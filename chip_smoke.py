"""Bring-up check of the rail-sweep compiler on one TPU chip.

Compiles the paper's setting on ``backend="jax"`` through
``CompileService.compile_many``: the four networks of ``EDGE_NETWORKS``
at 0.9 x their max rate (policy ``pfdnn``, ``n_max_rails=3``, the
accelerator's default 9-level grid), an 8-point ``ParetoFront`` and a
``MinLatency`` request on resnet18, all as ONE batch.  The batch runs
twice on the same service with the schedule cache off: a cold pass that
uploads every rail subset's lane tensors to the device, then a warm
pass that must run entirely from the resident device lanes.

Every chip schedule is held to the numpy backend (compiled in this
process; it never touches the device) field by field — rails, per-layer
voltages, ``e_total`` and ``t_infer``, compared exactly — and certified
by ``repro.analysis.certify``.  Any difference, violation or missing
device traffic fails the run.

    python chip_smoke.py

The script refuses to run anywhere but on a TPU: it exits non-zero,
printing no result, when JAX finds no TPU or when the repository's
``src/`` is not next to it.  Earlier lines report the walls, the
backend's transfer counters and the compilation-cache traffic; the last
line of stdout is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
NETWORKS = ("squeezenet1.1", "mobilenetv3-small", "resnet18",
            "mobilevit-xxs")
FRONTIER_NET = "resnet18"
RATE_FRAC = 0.9
N_MAX_RAILS = 3
N_FRONTIER = 8
# MinLatency budget: this multiple of the inference energy of the
# network's MinEnergy schedule (the rule of benchmarks/goals_smoke.py)
BUDGET_FACTOR = 1.3


def build_requests(backend: str, budget_j: float | None, *,
                   networks=NETWORKS, frontier_net=FRONTIER_NET,
                   n_frontier=N_FRONTIER, n_max_rails=N_MAX_RAILS):
    """The smoke batch on ``backend``: one MinEnergy request per network,
    then the frontier, then (given a budget) the MinLatency request."""
    from benchmarks.common import max_rate
    from repro.core import OrchestratorConfig
    from repro.models.edge_cnn import edge_network
    from repro.service import CompileRequest, MinLatency, ParetoFront

    cfg = OrchestratorConfig(policy="pfdnn", n_max_rails=n_max_rails,
                             backend=backend)
    reqs = [CompileRequest(edge_network(net), max_rate(net) * RATE_FRAC,
                           cfg, network=net) for net in networks]
    reqs.append(CompileRequest(edge_network(frontier_net), cfg=cfg,
                               network=frontier_net,
                               goal=ParetoFront(n_points=n_frontier)))
    if budget_j is not None:
        reqs.append(CompileRequest(edge_network(frontier_net), cfg=cfg,
                                   network=frontier_net,
                                   goal=MinLatency(budget_j)))
    return reqs


def _label(req) -> str:
    goal = type(req.goal).__name__ if req.goal is not None else \
        f"MinEnergy@{RATE_FRAC}x"
    return f"{req.network}|{goal}"


def _schedules(value) -> list:
    """A compile result as a list of point values (a frontier has one
    per deadline; anything else is one point)."""
    from repro.core.goals import ParetoFrontier

    if isinstance(value, ParetoFrontier):
        return value.schedules()
    return [value]


def _rel(a: float, b: float) -> float:
    return abs(a - b) / abs(b) if b else abs(a - b)


def differences(got, ref) -> list[str]:
    """Every field on which a chip result differs from the numpy one,
    with the size of the difference (empty when bit-identical)."""
    from repro.core.schedule import PowerSchedule

    out = []
    gs, rs = _schedules(got), _schedules(ref)
    if len(gs) != len(rs):
        return [f"{len(gs)} points vs numpy {len(rs)}"]
    for j, (g, r) in enumerate(zip(gs, rs)):
        at = f"point {j}: " if len(rs) > 1 else ""
        if not (isinstance(g, PowerSchedule)
                and isinstance(r, PowerSchedule)):
            if g != r:
                out.append(f"{at}{g!r} vs numpy {r!r}")
            continue
        if g.rails != r.rails:
            out.append(f"{at}rails {g.rails} vs numpy {r.rails}")
        n_lv = sum(a != b for a, b in zip(g.layer_voltages,
                                          r.layer_voltages))
        if n_lv or len(g.layer_voltages) != len(r.layer_voltages):
            out.append(f"{at}{n_lv} of {len(r.layer_voltages)} layer "
                       "states differ")
        for field in ("e_total", "t_infer"):
            a, b = getattr(g, field), getattr(r, field)
            if a != b:
                out.append(f"{at}{field} {a!r} vs numpy {b!r} "
                           f"(rel {_rel(a, b):.3e})")
    return out


def certify_all(value, req, n_max_rails: int) -> list[str]:
    """Certifier violations of every schedule in a chip result."""
    from repro.analysis.certify import certify
    from repro.core.schedule import PowerSchedule

    out = []
    for sched in _schedules(value):
        if not isinstance(sched, PowerSchedule):
            out.append(f"no schedule: {sched!r}")
            continue
        cert = certify(sched, req.specs, n_max_rails=n_max_rails)
        out.extend(str(v) for v in cert.violations)
    return out


def smoke(**sizes) -> tuple[list[str], dict]:
    """Run the reference, cold and warm passes; returns the failures
    (empty on success) and the measurements.  ``sizes`` (the keyword
    arguments of :func:`build_requests`) shrink the batch for a CPU
    rehearsal of the control flow."""
    from repro.core import get_backend
    from repro.service import CompileService

    n_max_rails = sizes.get("n_max_rails", N_MAX_RAILS)
    failures: list[str] = []
    report: dict = {}

    # numpy reference: the host backend, never the device
    # (the MinLatency budget derives from the frontier network's
    # MinEnergy schedule, so that request is compiled last)
    networks = list(sizes.get("networks", NETWORKS))
    tic = time.perf_counter()
    with CompileService(use_schedule_cache=False) as host:
        ref = host.compile_many(build_requests("numpy", None, **sizes))
        anchor = ref[networks.index(sizes.get("frontier_net",
                                              FRONTIER_NET))]
        budget = BUDGET_FACTOR * (anchor.e_op + anchor.e_trans)
        ref += host.compile_many(
            build_requests("numpy", budget, **sizes)[len(ref):])
    report["numpy_reference_wall_s"] = time.perf_counter() - tic

    reqs = build_requests("jax", budget, **sizes)
    jb = get_backend("jax")
    passes = {}
    with CompileService(use_schedule_cache=False) as svc:
        for name in ("cold", "warm"):
            before = dict(jb.io_stats)
            tic = time.perf_counter()
            passes[name] = svc.compile_many(reqs)
            report[f"{name}_wall_s"] = time.perf_counter() - tic
            report[f"{name}_io"] = {k: jb.io_stats[k] - before[k]
                                    for k in before}
            print(f"[{name}] {len(reqs)} requests  wall "
                  f"{report[f'{name}_wall_s']!r} s  io "
                  f"{report[f'{name}_io']}", flush=True)

    for name, values in passes.items():
        for req, got, want in zip(reqs, values, ref):
            diff = differences(got, want)
            bad = certify_all(got, req, n_max_rails)
            print(f"[{name}] {_label(req)}: "
                  f"{'identical to numpy' if not diff else 'DIFFERS'}, "
                  f"{'certified' if not bad else 'NOT CERTIFIED'}")
            failures += [f"{name} {_label(req)} vs numpy: {d}"
                         for d in diff]
            failures += [f"{name} {_label(req)} certify: {v}"
                         for v in bad]

    cold, warm = report["cold_io"], report["warm_io"]
    if cold["h2d_lane_uploads"] <= 0 or cold["kernel_dispatches"] <= 0:
        failures.append(f"cold pass ran no device lane work: {cold}")
    if warm["h2d_lane_uploads"] != 0:
        failures.append(f"warm pass uploaded lanes again: {warm}")
    if warm["kernel_dispatches"] <= 0:
        failures.append(f"warm pass dispatched no kernels: {warm}")
    return failures, report


def _compile_summary(events, min_compile_s: float) -> dict:
    """The programs JAX built (each records a compile time, also when
    it was loaded from the persistent cache) and the cache's hits; the
    cache's writes show in its entry counts."""
    c = sorted(events.compile_s)
    return {"cache_hits": events.hits, "backend_compiles": len(c),
            "compile_s_total": sum(c),
            "compile_s_median": c[len(c) // 2] if c else None,
            "compile_s_max": c[-1] if c else None,
            "compiles_at_or_above_cache_threshold":
                sum(s >= min_compile_s for s in c)}


def main() -> int:
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.core.backend import (configure_compile_cache,
                                        local_tpu_chips)
        import benchmarks.common  # noqa: F401
        from chipbench.device import CompileEvents
    except ImportError as exc:
        print(f"chip_smoke: the repository is not next to this script "
              f"({exc})", file=sys.stderr)
        return 2
    cache_dir = configure_compile_cache()
    import jax

    events = CompileEvents()
    try:
        devices = jax.devices()
    except RuntimeError as exc:
        print(f"chip_smoke: JAX found no device: {exc}", file=sys.stderr)
        return 1
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{dev.platform!r}); this check runs only on a TPU",
              file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"device: {device}", flush=True)
    cache_before = sum(1 for _ in pathlib.Path(cache_dir).rglob("*"))

    failures, report = smoke()

    # CompileFarm places its jax workers by this count: it must see the
    # chip JAX opened, or a farm here would start workers that hang
    chips = local_tpu_chips()
    print(f"local_tpu_chips: {chips}")
    if chips < 1:
        failures.append(f"local_tpu_chips() found no chip, JAX sees "
                        f"{len(devices)} TPU devices")
    min_s = jax.config.values["jax_persistent_cache_min_compile_time_secs"]
    report["compile_cache"] = dict(
        _compile_summary(events, min_s), dir=cache_dir,
        min_compile_time_s=min_s,
        entries_before=cache_before,
        entries_after=sum(1 for _ in pathlib.Path(cache_dir).rglob("*")))
    print(f"compile cache: {report['compile_cache']}")
    print(f"report: {json.dumps(report)}")
    if failures:
        for f in failures:
            print(f"FAIL {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
