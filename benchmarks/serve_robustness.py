"""Online serving robustness: adaptive control plane vs static schedule.

A/B-compares two deployments of the same compiled artifact set on
identical seeded traffic + fault traces (schedule-independent: both
sides see the exact same arrivals, drops, late frames, and cost-model
perturbations):

  - ``static``   — the paper's deployment: one schedule compiled for
    the provisioned base rate, replayed every interval, no reaction;
  - ``adaptive`` — the control plane: snap-to-frontier over a
    precompiled :class:`ContingencyBundle` (ONE ``compile_many`` fleet
    call up front), graceful-degradation ladder on miss-rate breach,
    hysteretic recovery.

Both sides provision at the same utilization target (``UTIL``): the
static point is compiled for ``base_rate / UTIL`` and the plane snaps
against ``UTIL × observed interval`` — nobody gets free headroom.

Scenarios (seeded, identical horizon for energy comparability):

  - ``calm``   — exactly periodic at the base rate (drops only): the
    plane must sit on the static point (energy parity within 1%);
  - ``bursty`` — calm → 1.25× burst → 0.4× lull phases with arrival
    jitter and the full fault set: the plane must deliver a strictly
    lower deadline-miss rate at equal-or-lower energy (burst premium
    paid for by lull relaxation);
  - ``drift``  — calm traffic under a ramping layer-cost error (up,
    then back down): the degradation ladder absorbs the drift and
    recovers hysteretically.

A fourth row, ``drift_learned``, replays the exact drift trace with
ledger-learned recalibration enabled (``repro.calib``): the plane
regresses executed-vs-predicted cost residuals and re-solves the
contingency set under the learned :class:`CalibratedCostModel`, so it
re-centers on the drifted optimum instead of paying the tightened-rung
energy premium for the whole excursion.  Acceptance: drift_learned
must cut the drift energy premium at an equal-or-better miss rate.

Every adaptive snap must resolve from a precompiled point (asserted
from the event log — the serving loop never blocks on a compile;
``drift_learned``'s re-solves are explicit ``calibrate_*`` events, and
its snaps still resolve from the re-centered precompiled set).

Usage:
    PYTHONPATH=src python benchmarks/serve_robustness.py \
        [--out BENCH_serve.json] [--smoke] \
        [--backend numpy|jax|jax-pallas-interpret] \
        [--frames N]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import time

try:
    from benchmarks._host import host_meta
except ImportError:  # direct script run: benchmarks/ is sys.path[0]
    from _host import host_meta

from repro.core import OrchestratorConfig
from repro.core.backend import configure_compile_cache
from repro.hw.edge40nm import EDGE40NM_DEFAULT as ACC
from repro.models.edge_cnn import edge_network
from repro.perfmodel import characterize_network, plan_banks
from repro.serve import (
    AdaptiveConfig,
    AdaptiveScheduler,
    FaultConfig,
    FaultInjector,
    StaticSchedulePolicy,
    TrafficConfig,
    TrafficSimulator,
    linear_drift,
    serve_trace,
)
from repro.service import CompileService

HERE = pathlib.Path(__file__).parent

NETWORK = "squeezenet1.1"
BASE_RATE_HZ = 60.0
UTIL = 0.85           # provisioning headroom, both deployments
TIGHTEN_FRAC = 0.92   # contingency rung: deadline-tightened variants
POLICY = "pfdnn"
SEED = 11


def scenario_plan(n_frames: int) -> dict[str, dict]:
    """Traffic + fault configuration per scenario (seeded; the traces
    are schedule-independent, so static and adaptive replay them
    identically)."""
    return {
        "calm": dict(
            traffic=TrafficConfig(BASE_RATE_HZ, scenario="calm"),
            faults=FaultConfig(seed=SEED, p_drop=0.01),
            bias=None),
        "bursty": dict(
            traffic=TrafficConfig(
                BASE_RATE_HZ, scenario="bursty", seed=3,
                jitter_sigma=0.05, burst_rate_mult=1.25,
                lull_rate_mult=0.4),
            faults=FaultConfig(
                seed=SEED, op_sigma=0.02, trans_sigma=0.1,
                p_trans_spike=0.02, p_drop=0.01, p_late=0.01,
                late_max_s=0.003),
            bias=None),
        "drift": dict(
            traffic=TrafficConfig(BASE_RATE_HZ, scenario="calm"),
            faults=FaultConfig(seed=SEED, op_sigma=0.01),
            # layer-cost error ramps to +30% at mid-trace, then back
            # down: exercises degrade AND hysteretic recovery at any
            # horizon length
            bias=linear_drift(0.3 / (n_frames // 2),
                              peak=n_frames // 2)),
    }


def report_row(report) -> dict:
    row = dataclasses.asdict(report)
    row.pop("events")
    return row


def run_scenarios(n_frames: int, backend: str | None) -> dict:
    specs = edge_network(NETWORK)
    costs = characterize_network(specs, ACC)
    plan = plan_banks(costs, ACC)
    cfg = OrchestratorConfig(policy=POLICY, backend=backend)

    # the whole contingency set — frontier grid, tightened variants,
    # aggressive point, energy-budget point — in ONE fleet call; the
    # service stays open for the drift_learned row, whose blocking
    # recalibration re-solves compile through it mid-trace
    tic = time.perf_counter()
    with CompileService(ACC) as svc:
        bundle = svc.compile_contingencies(
            specs, BASE_RATE_HZ / UTIL, tighten_frac=TIGHTEN_FRAC,
            cfg=cfg, network=NETWORK)
        bundle_wall = time.perf_counter() - tic
        static_sched = bundle.points[bundle.base_deadline_s]
        return _run_scenario_rows(
            svc, specs, costs, plan, cfg, bundle, bundle_wall,
            static_sched, n_frames)


def _run_scenario_rows(svc, specs, costs, plan, cfg, bundle,
                       bundle_wall, static_sched, n_frames) -> dict:

    results: dict = {
        "network": NETWORK, "policy": POLICY,
        "base_rate_hz": BASE_RATE_HZ, "util_target": UTIL,
        "n_frames": n_frames,
        "bundle": {
            "wall_s": bundle_wall,
            "n_points": len(bundle.points),
            "n_tightened": len(bundle.tightened),
            "deadlines_ms": [d * 1e3 for d in bundle.deadlines()],
            "aggressive_t_infer_ms": bundle.aggressive.t_infer * 1e3
            if bundle.aggressive else None,
            "infeasible": [tag for tag, _, _ in bundle.infeasible],
        },
        "scenarios": {},
    }

    n_layers = len(costs)
    for name, sc in scenario_plan(n_frames).items():
        times = TrafficSimulator(sc["traffic"]).frame_times(n_frames)

        def injector():
            return FaultInjector(sc["faults"], n_layers,
                                 op_bias=sc["bias"])

        static = serve_trace(
            times, StaticSchedulePolicy(static_sched, costs, plan, ACC),
            injector=injector())
        ada_policy = AdaptiveScheduler(bundle, costs, plan, ACC)
        adaptive = serve_trace(times, ada_policy, injector=injector())

        snaps = adaptive.events.of("snap")
        row = {
            "static": report_row(static),
            "adaptive": report_row(adaptive),
            "energy_ratio": adaptive.energy_j / static.energy_j,
            "events": adaptive.events.kinds(),
            "all_snaps_precompiled": bool(snaps) and all(
                e.detail.get("precompiled") for e in snaps),
        }
        results["scenarios"][name] = row
        print(f"{name:8s} static:   {static.summary()}")
        print(f"{name:8s} adaptive: {adaptive.summary()}")
        print(f"{name:8s} events: {row['events']}  "
              f"energy {100 * (row['energy_ratio'] - 1):+.2f}%")

    # drift_learned: the identical drift trace, but the plane learns a
    # CalibratedCostModel from its interval ledgers and re-solves the
    # contingency set (blocking: trace time is simulated, so an inline
    # compile costs no trace time — production uses the async path).
    # merge_points mutates the bundle, so this row runs on a copy.
    sc_drift = scenario_plan(n_frames)["drift"]
    times = TrafficSimulator(sc_drift["traffic"]).frame_times(n_frames)
    learned_bundle = dataclasses.replace(
        bundle, points=dict(bundle.points),
        tightened=dict(bundle.tightened),
        infeasible=list(bundle.infeasible))
    # the 15% provisioning headroom (UTIL) exists to absorb cost-model
    # error; a plane that *measures* that error needs less of it.  The
    # learned row provisions at 0.95 — the remaining margin covers the
    # estimator's tracking lag (window-median over a moving ramp) and
    # residual op noise.
    # the re-solved grid must put a point just inside the snap ceiling
    # (util 0.95 × snap_eps 1.05 ≈ the true interval): band (0.5, 1.8)
    # × 10 points lands one at ~0.96 × interval, so the calibrated
    # plane *executes* right at the deadline instead of 8% under it —
    # that executed slack is exactly the energy the static-model plane
    # burns as tightened-rung premium.  The short window/cooldown and
    # the 2% trigger keep the applied correction close enough to the
    # moving truth that the near-deadline point stays safe
    # (window-median lag + cooldown drift must fit in its margin).
    acfg = AdaptiveConfig(calib_enabled=True, calib_blocking=True,
                          util_target=0.95, resolve_points=10,
                          resolve_rate_band=(0.5, 1.8),
                          calib_window=16, calib_min_samples=8,
                          calib_cooldown=8, calib_threshold=0.02)
    learned_plane = AdaptiveScheduler(
        learned_bundle, costs, plan, ACC, service=svc, specs=specs,
        compile_cfg=cfg, acfg=acfg)
    learned = serve_trace(
        times, learned_plane,
        injector=FaultInjector(sc_drift["faults"], len(costs),
                               op_bias=sc_drift["bias"]))
    snaps = learned_plane.events.of("snap")
    drift_static_energy = \
        results["scenarios"]["drift"]["static"]["energy_j"]
    row = {
        "adaptive": report_row(learned),
        "energy_ratio": learned.energy_j / drift_static_energy,
        "events": learned.events.kinds(),
        "n_recalibrations": len(
            learned_plane.events.of("calibrate_done")),
        "all_snaps_precompiled": bool(snaps) and all(
            e.detail.get("precompiled") for e in snaps),
    }
    results["scenarios"]["drift_learned"] = row
    print(f"learned  adaptive: {learned.summary()}")
    print(f"learned  events: {row['events']}  "
          f"energy {100 * (row['energy_ratio'] - 1):+.2f}%  "
          f"recalibrations: {row['n_recalibrations']}")

    sc = results["scenarios"]
    results["acceptance"] = {
        "drift_learned_energy_improved":
            sc["drift_learned"]["energy_ratio"]
            < sc["drift"]["energy_ratio"],
        "drift_learned_miss_leq":
            sc["drift_learned"]["adaptive"]["miss_rate"]
            <= sc["drift"]["adaptive"]["miss_rate"] + 1e-9,
        "drift_learned_recalibrated":
            sc["drift_learned"]["n_recalibrations"] > 0,
        "bursty_miss_improved":
            sc["bursty"]["adaptive"]["miss_rate"]
            < sc["bursty"]["static"]["miss_rate"],
        "bursty_energy_leq":
            sc["bursty"]["energy_ratio"] <= 1.0 + 1e-9,
        "calm_energy_within_1pct":
            abs(sc["calm"]["energy_ratio"] - 1.0) <= 0.01,
        "drift_miss_improved":
            sc["drift"]["adaptive"]["miss_rate"]
            < sc["drift"]["static"]["miss_rate"],
        "all_snaps_precompiled": all(
            row["all_snaps_precompiled"] for row in sc.values()),
    }
    for key, val in results["acceptance"].items():
        print(f"{key}: {val}")
    return results


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out",
                    default=str(HERE.parent / "BENCH_serve.json"))
    ap.add_argument("--smoke", action="store_true",
                    help="short horizon; assert the acceptance block "
                         "and exit without writing the JSON")
    ap.add_argument("--backend", default=None,
                    choices=("numpy", "jax", "jax-pallas-interpret"),
                    help="solver array backend for the contingency "
                         "compile (default: $PFDNN_BACKEND or numpy)")
    ap.add_argument("--frames", type=int, default=None,
                    help="trace length (default 420; smoke 180)")
    args = ap.parse_args()
    configure_compile_cache()

    tic = time.perf_counter()
    n_frames = args.frames or (180 if args.smoke else 420)
    results = run_scenarios(n_frames, args.backend)
    if args.smoke:
        acc = results["acceptance"]
        assert acc["bursty_miss_improved"], \
            "adaptive plane did not beat the static schedule on bursty"
        assert acc["calm_energy_within_1pct"], \
            "adaptive plane broke calm energy parity"
        assert acc["all_snaps_precompiled"], \
            "a schedule snap did not resolve from a precompiled point"
        assert acc["drift_learned_recalibrated"], \
            "ledger-learned plane never re-solved under drift"
        assert acc["drift_learned_energy_improved"], \
            "learned recalibration did not cut the drift energy premium"
        assert acc["drift_learned_miss_leq"], \
            "learned recalibration regressed the drift miss rate"
        print(f"serve robustness smoke OK "
              f"({time.perf_counter() - tic:.1f}s)")
        return
    results["backend"] = args.backend or "default"
    results["host"] = host_meta(args.backend)
    pathlib.Path(args.out).write_text(json.dumps(results, indent=1))
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
