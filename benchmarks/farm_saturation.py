"""Saturation benchmark of the multi-tenant compile farm.

Queues >=1000 compile requests from four tenants with different traffic
shapes (a repeat-heavy burst, a broad batch sweep, an interactive
trickle, and an energy-budget dual tenant) against a
:class:`~repro.service.CompileFarm` — multi-process workers over one
shared on-disk artifact store — and records end-to-end queue latency
(enqueue -> result receipt) per request:

  - ``cold_solo``  — the pre-farm baseline: each *distinct* point's
    cold solo compile wall is measured (fresh store, no sharing), then
    the full trace is replayed serially through those measured walls
    (a repeat pays its point's full recompile — exactly what a
    store-less deployment does).  The modeled serial timeline gives
    queue-inclusive latencies comparable to the farm's;
  - ``cold_farm``  — a fresh store directory: workers pay every
    distinct solve once between them, repeats answer from the shared
    store;
  - ``warm_farm``  — a second farm with *fresh worker processes* over
    the same directory: every artifact is a cross-process disk hit
    (``counters()["disk_hits"]``), nothing is recompiled;
  - ``scaling``    — cold farms at 1..N workers over fresh directories
    on a shorter trace (same mix), the worker-count row;
  - ``parity``     — for every distinct point, the farm schedule is
    compared field-by-field against a solo ``compile()`` (bit
    identity, the guarantee the store's content addressing makes).

The farm phases run first and the solo phases after every farm process
has exited: on the jax backend the process that first touches jax owns
the host's TPU chips, so the parent stays off jax until its workers are
gone.

Acceptance (asserted in the full run AND recorded in the JSON):
shared-warm fleet p50 is >=10x faster than the cold-solo p50; no
tenant's p99 exceeds 3x the fleet p99 (fair-share admission under
mixed load); every farm schedule is bit-identical to solo.

Usage:
    PYTHONPATH=src python benchmarks/farm_saturation.py \
        [--out BENCH_farm.json] [--smoke] [--requests N] \
        [--workers N] [--backend numpy|jax|jax-pallas-interpret]

``--smoke`` is the CI guard: a small request count on 2 workers
(numpy backend), asserting solo parity and a nonzero cross-process
disk hit rate, without writing the JSON.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import shutil
import tempfile
import time

import numpy as np

try:
    from benchmarks.common import max_rate
    from benchmarks._host import host_meta
except ImportError:  # direct script run: benchmarks/ is sys.path[0]
    from common import max_rate
    from _host import host_meta

from repro.core import OrchestratorConfig
from repro.core.backend import configure_compile_cache
from repro.models.edge_cnn import edge_network
from repro.service import (
    CompileFarm,
    CompileRequest,
    CompileService,
    MinLatency,
    latency_summary,
)

HERE = pathlib.Path(__file__).parent
N_RAILS = 2
_SPECS: dict[str, list] = {}


def specs_for(network: str):
    if network not in _SPECS:
        _SPECS[network] = edge_network(network)
    return _SPECS[network]


@dataclasses.dataclass(frozen=True)
class Point:
    """One distinct deployment point: a rate target (MinEnergy) or an
    energy budget (MinLatency dual)."""

    name: str
    network: str
    policy: str
    frac: float | None = None
    energy_budget_j: float | None = None

    def cfg(self, backend: str | None) -> OrchestratorConfig:
        return OrchestratorConfig(policy=self.policy,
                                  n_max_rails=N_RAILS, backend=backend)

    def request(self, backend: str | None) -> CompileRequest:
        if self.energy_budget_j is not None:
            return CompileRequest(
                specs_for(self.network), cfg=self.cfg(backend),
                network=self.network,
                goal=MinLatency(self.energy_budget_j))
        return CompileRequest(
            specs_for(self.network),
            max_rate(self.network) * self.frac, self.cfg(backend),
            network=self.network)

    def solo(self, backend: str | None):
        """Cold solo compile: a fresh memory-only service — the
        pre-farm deployment shape and the parity reference."""
        svc = CompileService()
        req = self.request(backend)
        if req.goal is not None:
            return svc.compile(req.specs, cfg=req.cfg,
                               network=req.network, goal=req.goal)
        return svc.compile(req.specs, req.target_rate_hz, cfg=req.cfg,
                           network=req.network)


def build_points(smoke: bool) -> list[Point]:
    rate_grid = [("squeezenet1.1", 0.9), ("squeezenet1.1", 0.7),
                 ("squeezenet1.1", 0.5), ("mobilenetv3-small", 0.85),
                 ("mobilenetv3-small", 0.6)]
    policies = ("pfdnn",) if smoke else ("pfdnn", "greedy_gating")
    points = [Point(f"{net}|{frac}|{pol}", net, pol, frac=frac)
              for net, frac in rate_grid for pol in policies]
    # energy-budget duals (budgets sit comfortably above each
    # network's min-deadline energy, so the points are feasible)
    points.append(Point("squeezenet1.1|budget|pfdnn", "squeezenet1.1",
                        "pfdnn", energy_budget_j=4.0e-4))
    if not smoke:
        points.append(Point("mobilenetv3-small|budget|pfdnn",
                            "mobilenetv3-small", "pfdnn",
                            energy_budget_j=1.2e-4))
    return points


def build_trace(points: list[Point],
                n_requests: int) -> dict[str, list[Point]]:
    """Four tenants, four traffic shapes, ``n_requests`` total.  The
    burst tenant hammers 3 points with 60 % of the volume — the load
    fair-share admission must keep from starving everyone else."""
    duals = [p for p in points if p.energy_budget_j is not None]
    mixes = {
        "burst": (points[:3], 0.60),
        "batch": (points, 0.25),
        "interactive": (points[::2], 0.10),
        "duals": (duals or points[:1], 0.05),
    }
    trace: dict[str, list[Point]] = {}
    assigned = 0
    for i, (tenant, (pts, share)) in enumerate(mixes.items()):
        n = n_requests - assigned if i == len(mixes) - 1 \
            else int(n_requests * share)
        trace[tenant] = [pts[j % len(pts)] for j in range(n)]
        assigned += n
    return trace


def run_farm(root, trace: dict[str, list[Point]], *, workers: int,
             backend: str | None, batch_size: int = 32):
    """One farm pass over the trace; returns (results-by-uid, the
    uid -> Point map, aggregate counters, drain wall)."""
    uid_to_point: dict[int, Point] = {}
    with CompileFarm(root, n_workers=workers, batch_size=batch_size,
                     backend=backend) as farm:
        for tenant, pts in trace.items():
            uids = farm.submit(tenant,
                               [p.request(backend) for p in pts])
            uid_to_point.update(zip(uids, pts))
        tic = time.perf_counter()
        results = farm.drain()
        wall = time.perf_counter() - tic
        counters = farm.counters()
    errors = [r.error for r in results.values() if r.error]
    assert not errors, f"farm reported errors: {errors[:3]}"
    return results, uid_to_point, counters, wall


def cold_solo_phase(points: list[Point],
                    trace: dict[str, list[Point]],
                    backend: str | None) -> dict:
    """Measured per-point cold walls + the modeled serial replay of the
    full trace (see module docstring)."""
    walls: dict[str, float] = {}
    for p in points:
        tic = time.perf_counter()
        sched = p.solo(backend)
        walls[p.name] = time.perf_counter() - tic
        assert sched is not None and getattr(sched, "feasible", True), \
            f"cold solo compile of {p.name} was infeasible"
    # serial replay: requests in submission order, each paying its
    # point's full recompile; latency is queue-inclusive completion
    per_tenant: dict[str, list[float]] = {}
    t = 0.0
    for tenant, pts in trace.items():
        for p in pts:
            t += walls[p.name]
            per_tenant.setdefault(tenant, []).append(t)
    fleet = [lat for lats in per_tenant.values() for lat in lats]

    def summarize(lat):
        arr = np.array(lat)
        return {"n": len(lat),
                "p50_s": float(np.percentile(arr, 50)),
                "p99_s": float(np.percentile(arr, 99)),
                "mean_s": float(arr.mean()),
                "max_s": float(arr.max())}

    return {"methodology": "per-point cold walls measured once; the "
                           "trace is replayed serially (repeats pay "
                           "full recompiles — the store-less baseline)",
            "point_walls_s": walls,
            "serial_wall_s": t,
            "latency": {"fleet": summarize(fleet),
                        "tenants": {t_: summarize(l) for t_, l
                                    in sorted(per_tenant.items())}}}


_PARITY_FIELDS = ("rails", "layer_voltages", "e_total", "t_infer",
                  "feasible")


def schedule_diff(a, b) -> list[str]:
    """The fields on which two schedules differ (empty: bit-identical)."""
    if a is None or b is None:
        return [] if a is b else [f"schedule {a!r} vs {b!r}"]
    out = []
    for field in _PARITY_FIELDS:
        x, y = getattr(a, field), getattr(b, field)
        if x != y:
            out.append(field if field == "layer_voltages"
                       else f"{field} {x!r} vs {y!r}")
    return out


def parity_phase(points: list[Point], results: dict, uid_to_point,
                 backend: str | None) -> dict:
    """Every distinct point: farm schedule vs a solo ``compile()`` —
    bit-identical fields."""
    first_result = {}
    for uid, res in sorted(results.items()):
        first_result.setdefault(uid_to_point[uid].name, res)
    per_point = {}
    diffs = {}
    for p in points:
        diff = schedule_diff(first_result[p.name].value, p.solo(backend))
        per_point[p.name] = not diff
        if diff:
            diffs[p.name] = diff
            print(f"[parity] {p.name}: farm vs solo: {'; '.join(diff)}")
    return {"per_point": per_point, "diffs": diffs,
            "identical": all(per_point.values())}


def fairness_ok(summary: dict, factor: float = 3.0) -> bool:
    fleet_p99 = summary["fleet"]["p99_s"]
    return all(t["p99_s"] <= factor * fleet_p99
               for t in summary["tenants"].values())


def run(n_requests: int, workers: int, backend: str | None,
        smoke: bool) -> dict:
    points = build_points(smoke)
    trace = build_trace(points, n_requests)
    results: dict = {
        "n_requests": n_requests, "workers": workers,
        "n_points": len(points),
        "points": [p.name for p in points],
        "tenants": {t: len(pts) for t, pts in trace.items()},
        "batch_size": 32,
    }

    tmp = pathlib.Path(tempfile.mkdtemp(prefix="farm_bench_"))
    try:
        root = tmp / "store"
        print(f"[cold_farm] {n_requests} requests on {workers} "
              f"worker(s) ...")
        cold_res, cold_map, cold_counters, cold_wall = run_farm(
            root, trace, workers=workers, backend=backend)
        cold_lat = latency_summary(list(cold_res.values()))
        results["cold_farm"] = {"wall_s": cold_wall,
                                "latency": cold_lat,
                                "counters": cold_counters}
        print(f"[cold_farm] wall {cold_wall:.1f}s  "
              f"p50 {cold_lat['fleet']['p50_s']:.2f}s  "
              f"p99 {cold_lat['fleet']['p99_s']:.2f}s")

        print("[warm_farm] fresh processes over the same store ...")
        warm_res, warm_map, warm_counters, warm_wall = run_farm(
            root, trace, workers=workers, backend=backend)
        warm_lat = latency_summary(list(warm_res.values()))
        results["warm_farm"] = {"wall_s": warm_wall,
                                "latency": warm_lat,
                                "counters": warm_counters}
        print(f"[warm_farm] wall {warm_wall:.1f}s  "
              f"p50 {warm_lat['fleet']['p50_s']:.2f}s  "
              f"p99 {warm_lat['fleet']['p99_s']:.2f}s  "
              f"disk_hits {warm_counters['disk_hits']}")

        if not smoke:
            scaling = []
            short = build_trace(points, max(200, n_requests // 5))
            for w in range(1, workers + 1):
                wdir = tmp / f"scale{w}"
                res, _, _, wall = run_farm(wdir, short, workers=w,
                                           backend=backend)
                lat = latency_summary(list(res.values()))
                scaling.append({"workers": w, "n_requests":
                                sum(len(p) for p in short.values()),
                                "wall_s": wall,
                                "p50_s": lat["fleet"]["p50_s"],
                                "p99_s": lat["fleet"]["p99_s"]})
                print(f"[scaling] {w} worker(s): wall {wall:.1f}s")
            results["scaling"] = scaling
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    # every farm process has exited: the parent may touch jax now
    print(f"[cold_solo] measuring {len(points)} distinct points ...")
    results["cold_solo"] = cold_solo_phase(points, trace, backend)
    p50_solo = results["cold_solo"]["latency"]["fleet"]["p50_s"]
    print(f"[cold_solo] modeled serial p50 {p50_solo:.2f}s "
          f"(serial wall {results['cold_solo']['serial_wall_s']:.1f}s)")
    results["parity"] = parity_phase(points, warm_res, warm_map, backend)

    warm_p50 = warm_lat["fleet"]["p50_s"]
    results["acceptance"] = {
        "warm_p50_speedup_vs_cold_solo": p50_solo / warm_p50,
        "warm_p50_10x": warm_p50 * 10.0 <= p50_solo,
        "fairness_cold_farm": fairness_ok(cold_lat),
        "fairness_warm_farm": fairness_ok(warm_lat),
        "parity": results["parity"]["identical"],
        "cross_process_schedule_hits":
            warm_counters["disk_hits"].get("schedule", 0),
    }
    for key, val in results["acceptance"].items():
        print(f"{key}: {val if not isinstance(val, float) else f'{val:.1f}'}")
    return results


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=str(HERE.parent / "BENCH_farm.json"))
    ap.add_argument("--smoke", action="store_true",
                    help="small trace on 2 workers; assert solo parity "
                         "+ nonzero cross-process hits and exit")
    ap.add_argument("--requests", type=int, default=None,
                    help="queued request count (default 1000; smoke 24)")
    ap.add_argument("--workers", type=int, default=2,
                    help="farm worker processes (default 2)")
    ap.add_argument("--backend", default=None,
                    choices=("numpy", "jax", "jax-pallas-interpret"),
                    help="solver array backend inside the workers "
                         "(default: $PFDNN_BACKEND or numpy)")
    args = ap.parse_args()
    configure_compile_cache()

    tic = time.perf_counter()
    n_requests = args.requests or (24 if args.smoke else 1000)
    results = run(n_requests, args.workers, args.backend, args.smoke)
    if args.smoke:
        acc = results["acceptance"]
        assert acc["parity"], \
            "a farm schedule diverged from its solo compile"
        assert acc["cross_process_schedule_hits"] > 0, \
            "second farm saw no cross-process schedule hits"
        assert acc["fairness_warm_farm"], \
            "a tenant's p99 exceeded 3x the fleet p99"
        print(f"farm saturation smoke OK "
              f"({time.perf_counter() - tic:.1f}s)")
        return
    acc = results["acceptance"]
    assert acc["warm_p50_10x"], \
        (f"shared-warm p50 not 10x faster than cold solo "
         f"({acc['warm_p50_speedup_vs_cold_solo']:.1f}x)")
    assert acc["parity"] and acc["fairness_cold_farm"] \
        and acc["fairness_warm_farm"]
    results["backend"] = args.backend or "default"
    results["host"] = host_meta(args.backend)
    pathlib.Path(args.out).write_text(json.dumps(results, indent=1))
    print(f"wrote {args.out} ({time.perf_counter() - tic:.1f}s)")


if __name__ == "__main__":
    main()
