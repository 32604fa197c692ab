"""Wall-clock benchmark of the fleet compile service.

Measures what the process-wide artifact store and the cross-network
round scheduler buy over per-deployment compilation, on a fixed fleet
of deployment points (one accelerator, several networks × rates):

  - ``cold_sequential``  — plain per-request ``compile_power_schedule``
    (fresh context each time): the pre-service baseline;
  - ``cold_many_unstacked`` — a fresh ``CompileService.compile_many``
    with cross-network stacking off (store sharing only);
  - ``cold_many_stacked``   — a fresh ``compile_many`` with all rail
    sweeps co-scheduled in one round scheduler;
  - ``warm_solve``  — ``compile_many`` on the now-populated store with
    the schedule cache cleared: full solves through warm
    characterization / master / transition / lane-store artifacts;
  - ``warm_cached`` — repeat traffic: the persistent schedule cache
    answers every request;
  - ``pareto_frontier`` — one ``ParetoFront(deadlines=...)`` compile
    (all points co-scheduled as stacked sweeps on a fresh store) vs N
    independent cold ``compile_power_schedule`` calls at the same
    deadlines: the goal API's frontier row.

Every variant must emit schedules identical to ``cold_sequential``
(rails, per-layer voltages, energies) — recorded as ``identical`` in
the comparison block alongside the speedups; the frontier's per-point
schedules must equal the independent compiles.

Usage:
    PYTHONPATH=src python benchmarks/service_speed.py \
        [--out BENCH_service.json] [--smoke] \
        [--backend numpy|jax|jax-pallas-interpret] \
        [--reps N]

On the jax backends the ``cold_many_stacked`` / ``warm_solve`` rows
also record ``io_delta`` — the device-lane transfer counters over the
variant's last rep: warm solves on a populated store re-use the
device-resident lanes, so their ``h2d_lane_uploads`` delta is 0 while
``kernel_dispatches`` keeps counting.

``--smoke`` runs a two-request fleet (n_max_rails=2) as a CI guard:
schedules must be feasible and identical across all variants; no
timing is asserted.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import time

try:
    from benchmarks.common import max_rate, timed
    from benchmarks._host import host_meta
except ImportError:  # direct script run: benchmarks/ is sys.path[0]
    from common import max_rate, timed
    from _host import host_meta

from repro.core import (
    OrchestratorConfig,
    ParetoFront,
    compile_power_schedule,
)
from repro.core.backend import configure_compile_cache
from repro.models.edge_cnn import edge_network
from repro.service import CompileRequest, CompileService

HERE = pathlib.Path(__file__).parent

# (network, fraction of max rate, n_max_rails) — ≥3 deployment points on
# one accelerator, mixing distinct networks with shared-content repeats
# at other rates (the fleet shape the store amortizes across)
FLEET = [
    ("squeezenet1.1", 0.90, 3),
    ("mobilenetv3-small", 0.85, 3),
    ("squeezenet1.1", 0.50, 3),
]
SMOKE_FLEET = [
    ("squeezenet1.1", 0.90, 2),
    ("mobilenetv3-small", 0.85, 2),
]
POLICY = "pfdnn"
# frontier row: deadlines as fractions of one network's max rate
PARETO_NETWORK = "squeezenet1.1"
PARETO_FRACS = (0.9, 0.7, 0.5, 0.35)
SMOKE_PARETO_FRACS = (0.9, 0.5)


def build_requests(fleet, backend: str | None) -> list[CompileRequest]:
    reqs = []
    for network, frac, n_rails in fleet:
        reqs.append(CompileRequest(
            edge_network(network), max_rate(network) * frac,
            OrchestratorConfig(policy=POLICY, n_max_rails=n_rails,
                               backend=backend),
            network=f"{network}|{frac}"))
    return reqs


def same_schedules(a, b) -> bool:
    if len(a) != len(b):
        return False
    for x, y in zip(a, b):
        if (x is None) != (y is None):
            return False
        if x is not None and (
                x.rails != y.rails
                or x.layer_voltages != y.layer_voltages
                or x.e_total != y.e_total
                or x.t_infer != y.t_infer):
            return False
    return True


def _counters(store) -> dict:
    """Per-category hit/miss/disk-hit/evict counters — the cache-efficacy
    block attached to every bench row."""
    s = store.stats()
    return {grp: dict(s[grp])
            for grp in ("hits", "misses", "disk_hits", "evictions")}


def _counter_delta(after: dict, before: dict) -> dict:
    return {grp: {k: after[grp][k] - before[grp].get(k, 0)
                  for k in after[grp]} for grp in after}


def run_fleet(fleet, *, backend: str | None, reps: int) -> dict:
    from repro.core import get_backend

    results: dict = {"fleet": [f"{n}|{f}|r{k}" for n, f, k in fleet],
                     "policy": POLICY, "reps": reps}
    io = getattr(get_backend(backend), "io_stats", None)
    fresh = {}   # the last cold variant's service (fresh store per rep)

    def best_of(fn, n=reps, store=None):
        walls, out = [], None
        for _ in range(n):
            mark = dict(io) if io is not None else None
            cmark = _counters(store) if store is not None else None
            out, wall = timed(fn)
            walls.append(wall)
        # device-lane transfer + store counters over the LAST rep (see
        # module docstring); io is empty on host-only backends
        delta = {k: io[k] - mark[k] for k in io} \
            if io is not None else None
        cdelta = _counter_delta(_counters(store), cmark) \
            if store is not None else None
        return out, min(walls), walls, delta, cdelta

    def cold_sequential():
        reqs = build_requests(fleet, backend)
        return [compile_power_schedule(
            r.specs, r.target_rate_hz, cfg=r.cfg, network=r.network)
            for r in reqs]

    ref, wall, walls, _, _ = best_of(cold_sequential)
    results["cold_sequential"] = {"wall_s": wall, "wall_all_s": walls}

    def cold_many(stack: bool):
        def inner():
            svc = CompileService()              # fresh store: cold
            fresh["svc"] = svc
            return svc.compile_many(build_requests(fleet, backend),
                                    stack_networks=stack)
        return inner

    out_u, wall, walls, _, _ = best_of(cold_many(False))
    results["cold_many_unstacked"] = {"wall_s": wall,
                                      "wall_all_s": walls,
                                      "identical": same_schedules(out_u,
                                                                  ref),
                                      "store_counters":
                                      _counters(fresh["svc"].store)}
    out_s, wall, walls, io_s, _ = best_of(cold_many(True))
    results["cold_many_stacked"] = {"wall_s": wall, "wall_all_s": walls,
                                    "identical": same_schedules(out_s,
                                                                ref),
                                    "io_delta": io_s,
                                    "store_counters":
                                    _counters(fresh["svc"].store)}

    # one persistent service: populate, then measure the warm regimes
    svc = CompileService()
    svc.compile_many(build_requests(fleet, backend))

    def warm_solve():
        svc.store.clear(schedules=True, stacks=False, tables=False)
        return svc.compile_many(build_requests(fleet, backend))

    out_w, wall, walls, io_w, c_w = best_of(warm_solve, store=svc.store)
    results["warm_solve"] = {"wall_s": wall, "wall_all_s": walls,
                             "identical": same_schedules(out_w, ref),
                             "io_delta": io_w, "store_counters": c_w}

    svc.compile_many(build_requests(fleet, backend))   # refill the cache

    def warm_cached():
        return svc.compile_many(build_requests(fleet, backend))

    out_c, wall, walls, _, c_c = best_of(warm_cached, store=svc.store)
    results["warm_cached"] = {"wall_s": wall, "wall_all_s": walls,
                              "identical": same_schedules(out_c, ref),
                              "store_counters": c_c}
    results["store_stats"] = svc.store.stats()
    svc.close()

    # -- Pareto frontier: one goal-API compile (stacked sweeps sharing
    # one context + store) vs N independent cold compiles
    fracs = SMOKE_PARETO_FRACS if len(fleet) < 3 else PARETO_FRACS
    n_rails = fleet[0][2]
    specs = edge_network(PARETO_NETWORK)
    deadlines = tuple(1.0 / (max_rate(PARETO_NETWORK) * f)
                      for f in fracs)
    cfg = OrchestratorConfig(policy=POLICY, n_max_rails=n_rails,
                             backend=backend)

    def frontier_compile():
        return CompileService().compile(
            specs, cfg=cfg, network=PARETO_NETWORK,
            goal=ParetoFront(deadlines=deadlines))

    def independent_points():
        return [compile_power_schedule(specs, 1.0 / d, cfg=cfg,
                                       network=PARETO_NETWORK)
                for d in deadlines]

    front, wall_f, walls_f, _, _ = best_of(frontier_compile)
    solo, wall_s, walls_s, _, _ = best_of(independent_points)
    results["pareto_frontier"] = {
        "n_points": len(deadlines),
        "wall_s": wall_f, "wall_all_s": walls_f,
        "independent_wall_s": wall_s,
        "independent_wall_all_s": walls_s,
        "identical": same_schedules(
            [p.schedule if p.feasible else None
             for p in front.points], solo),
    }

    base = results["cold_sequential"]["wall_s"]
    results["comparison"] = {
        "speedup_cold_many_stacked": base
        / results["cold_many_stacked"]["wall_s"],
        "speedup_cold_many_unstacked": base
        / results["cold_many_unstacked"]["wall_s"],
        "speedup_warm_solve": base / results["warm_solve"]["wall_s"],
        "speedup_warm_cached": base / results["warm_cached"]["wall_s"],
        "stacked_vs_unstacked": results["cold_many_unstacked"]["wall_s"]
        / results["cold_many_stacked"]["wall_s"],
        "speedup_pareto_vs_independent":
        results["pareto_frontier"]["independent_wall_s"]
        / results["pareto_frontier"]["wall_s"],
        "identical": all(results[k]["identical"] for k in (
            "cold_many_unstacked", "cold_many_stacked", "warm_solve",
            "warm_cached", "pareto_frontier")),
    }
    for key, val in results["comparison"].items():
        print(f"{key}: {val if isinstance(val, bool) else f'{val:.2f}x'}")
    return results


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out",
                    default=str(HERE.parent / "BENCH_service.json"))
    ap.add_argument("--smoke", action="store_true",
                    help="two-request fleet; assert identical feasible "
                         "schedules across all variants and exit")
    ap.add_argument("--backend", default=None,
                    choices=("numpy", "jax", "jax-pallas-interpret"),
                    help="solver array backend (default: $PFDNN_BACKEND "
                         "or numpy); jax-pallas-interpret runs the "
                         "Pallas DP kernels in interpret mode; jax "
                         "backends record io_delta columns")
    ap.add_argument("--reps", type=int, default=3,
                    help="best-of-N walls per variant")
    args = ap.parse_args()
    configure_compile_cache()

    tic = time.perf_counter()
    fleet = SMOKE_FLEET if args.smoke else FLEET
    results = run_fleet(fleet, backend=args.backend,
                        reps=1 if args.smoke else args.reps)
    if args.smoke:
        assert results["comparison"]["identical"], \
            "service variants emitted different schedules"
        assert results["store_stats"]["schedules"] >= len(fleet), \
            "schedule cache did not populate"
        print(f"service smoke OK ({time.perf_counter() - tic:.1f}s)")
        return
    results["backend"] = args.backend or "default"
    results["host"] = host_meta(args.backend)
    pathlib.Path(args.out).write_text(json.dumps(results, indent=1))
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
