"""Wall-clock benchmark of the rail-subset sweep (compile_power_schedule).

Times the full-sweep policies (`pfdnn`, `pfdnn_nopp`, n_max_rails=3)
across the edge network configs and emits ``BENCH_sweep.json`` so future
PRs have a perf trajectory.

Usage:
    PYTHONPATH=src python benchmarks/sweep_speed.py \
        [--out BENCH_sweep.json] [--record-baseline] [--smoke] \
        [--backend numpy|jax|jax-pallas-interpret] \
        [--workers N]

``--record-baseline`` writes ``benchmarks/baseline_sweep.json`` instead
(run once against the implementation you want to compare against).  When
a baseline file exists, the default run folds it into the output and
reports per-config speedups plus whether rails/energy are identical;
when ``benchmarks/prev_sweep.json`` (the previous PR's ``current``
block) exists, per-config ``speedup_vs_prev`` is reported too.

``--smoke`` runs a single small config (n_max_rails=2) as a CI
completion guard: the sweep must produce a feasible schedule with
non-empty rails.  It runs a different rail budget than the recorded
baseline, so no energy comparison is made and no timing is asserted.
``--backend``/``--workers`` select the solver array backend and the
rail-sweep thread fan-out; both are recorded in every result row.
``--no-stack`` times the legacy per-subset sweep instead of the
subset-stacked engine.

The full run's ``comparison`` block carries per-config speedups and
``dp_calls``/``dp_lambdas`` deltas vs baseline and previous PR, plus a
``smoke_backends`` block with warm (post-jit) per-backend walls on the
smoke config (including the Pallas backends when jax is available).

Device columns (jax backends only, ``null`` under numpy): each result
row records the backend transfer counters for its LAST rep —
``h2d_lane_uploads`` / ``h2d_lane_bytes`` are host→device operand
uploads (one per newly admitted rail-subset lane; warm rounds add
zero) and ``kernel_dispatches`` counts device lane-kernel launches,
so bytes-per-dispatch ≈ 0 is the device-resident steady state.
"""

from __future__ import annotations

import argparse
import json
import pathlib

try:
    from benchmarks.common import max_rate, schedule_for, timed
    from benchmarks._host import host_meta
except ImportError:  # direct script run: benchmarks/ is sys.path[0]
    from common import max_rate, schedule_for, timed
    from _host import host_meta

from repro.core.backend import configure_compile_cache

HERE = pathlib.Path(__file__).parent
BASELINE_PATH = HERE / "baseline_sweep.json"
PREV_PATH = HERE / "prev_sweep.json"

CONFIGS = [
    ("squeezenet1.1", 0.90),
    ("mobilenetv3-small", 0.85),
]
SMOKE_CONFIGS = [("squeezenet1.1", 0.90)]
POLICIES = ("pfdnn", "pfdnn_nopp")
SMOKE_POLICIES = ("pfdnn",)
N_MAX_RAILS = 3


def run_sweeps(*, smoke: bool = False, backend: str | None = None,
               workers: int | None = None, reps: int = 5,
               stack: bool = True) -> dict[str, dict]:
    out: dict[str, dict] = {}
    configs = SMOKE_CONFIGS if smoke else CONFIGS
    policies = SMOKE_POLICIES if smoke else POLICIES
    n_rails = 2 if smoke else N_MAX_RAILS
    if smoke:
        reps = 1
    from repro.core import get_backend

    io = getattr(get_backend(backend), "io_stats", None)
    for network, frac in configs:
        rate = max_rate(network) * frac
        for policy in policies:
            key = f"{network}|{frac}|{policy}"
            walls = []
            for _ in range(reps):
                mark = dict(io) if io is not None else None
                s, wall = timed(schedule_for, network, rate, policy,
                                n_max_rails=n_rails, backend=backend,
                                sweep_workers=workers,
                                stack_subsets=stack)
                walls.append(wall)
            # device columns: transfer/dispatch deltas of the LAST rep
            # (see module docstring) — None on host-only backends
            io_row = {k: io[k] - mark[k] for k in io} \
                if io is not None else {}
            wall = min(walls)             # best-of-reps: noise guard
            stats = s.solver_stats if s is not None else {}
            out[key] = {
                "wall_s": wall,
                "wall_all_s": walls,
                "reps": reps,
                "e_total": s.e_total if s is not None else None,
                "rails": list(s.rails) if s is not None else None,
                "subsets_total": stats.get("subsets_total"),
                "subsets_solved": stats.get("subsets_solved"),
                "subsets_skipped": stats.get("subsets_skipped"),
                "subsets_cut": stats.get("subsets_cut"),
                "dp_calls": stats.get("dp_calls"),
                "dp_lambdas": stats.get("dp_lambdas"),
                "candidates_evaluated": stats.get("candidates_evaluated"),
                "backend": stats.get("backend", "numpy"),
                "workers": stats.get("workers", 1),
                "stacked_rounds": stats.get("stacked_rounds"),
                "stacked_calls": stats.get("stacked_calls"),
                "h2d_lane_uploads": io_row.get("h2d_lane_uploads"),
                "h2d_lane_bytes": io_row.get("h2d_lane_bytes"),
                "kernel_dispatches": io_row.get("kernel_dispatches"),
            }
            print(f"{key}: {wall:.2f}s  "
                  f"E={out[key]['e_total']}  rails={out[key]['rails']}  "
                  f"dp_calls={out[key]['dp_calls']}  "
                  f"backend={out[key]['backend']}  "
                  f"workers={out[key]['workers']}")
    return out


def compare(results: dict[str, dict], reference: dict[str, dict],
            *, against: str) -> dict[str, dict]:
    comparison: dict[str, dict] = {}
    for key, cur in results.items():
        base = reference.get(key)
        if not base:
            continue
        comparison[key] = {
            "speedup": base["wall_s"] / cur["wall_s"]
            if cur["wall_s"] > 0 else None,
            "same_rails": base["rails"] == cur["rails"],
            "same_energy": (
                base["e_total"] is None and cur["e_total"] is None) or (
                base["e_total"] is not None
                and cur["e_total"] is not None
                and abs(base["e_total"] - cur["e_total"])
                <= 1e-9 * abs(base["e_total"])),
        }
        # solver-work deltas: how much DP the engine saved, not just
        # how fast the wall got (wall is host-noise-sensitive)
        for stat in ("dp_calls", "dp_lambdas"):
            if base.get(stat) and cur.get(stat):
                comparison[key][f"{stat}_delta"] = {
                    "before": base[stat], "after": cur[stat],
                    "ratio": base[stat] / cur[stat]}
        print(f"{key} vs {against}: "
              f"speedup {comparison[key]['speedup']:.2f}x  "
              f"same_rails={comparison[key]['same_rails']}  "
              f"same_energy={comparison[key]['same_energy']}")
    return comparison


def bench_backends() -> list[str]:
    """Backends the bench can exercise here: the registry's names plus
    the Pallas interpret mode whenever jax is importable."""
    from repro.core.backend import available_backends

    names = list(available_backends())
    if "jax" in names:
        names.append("jax-pallas-interpret")
    return names


def smoke_backend_compare(reps: int = 3) -> dict[str, dict]:
    """Warm per-backend walls on the smoke config (first compile per
    backend is discarded — it pays one-time jit compilation).  Records
    the 'jax no longer slower than numpy' claim of the stacked sweep,
    with the device transfer columns per backend, and asserts every
    backend reproduces the numpy schedule bit-for-bit (the stacked
    kernel parity guard)."""
    from repro.core import get_backend

    (network, frac), = SMOKE_CONFIGS
    rate = max_rate(network) * frac
    out: dict[str, dict] = {}
    for backend in bench_backends():
        schedule_for(network, rate, "pfdnn", n_max_rails=2,
                     backend=backend)                        # warm-up
        io = getattr(get_backend(backend), "io_stats", None)
        walls = []
        for _ in range(reps):
            mark = dict(io) if io is not None else None
            s, wall = timed(schedule_for, network, rate, "pfdnn",
                            n_max_rails=2, backend=backend)

            walls.append(wall)
        out[backend] = {"wall_s": min(walls), "wall_all_s": walls,
                        "e_total": s.e_total, "rails": list(s.rails)}
        if io is not None:
            out[backend].update(
                {k: io[k] - mark[k] for k in io})
        ref = out["numpy"]
        assert (s.e_total == ref["e_total"]
                and list(s.rails) == ref["rails"]), \
            f"{backend} smoke schedule diverged from numpy"
        print(f"smoke[{backend}]: {min(walls):.3f}s warm (best of {reps})")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=str(HERE.parent / "BENCH_sweep.json"))
    ap.add_argument("--record-baseline", action="store_true",
                    help="write benchmarks/baseline_sweep.json instead")
    ap.add_argument("--smoke", action="store_true",
                    help="one small config; assert the sweep emits a "
                         "feasible schedule and exit (CI guard)")
    ap.add_argument("--backend", default=None,
                    choices=("numpy", "jax", "jax-pallas-interpret"),
                    help="solver array backend (default: $PFDNN_BACKEND "
                         "or numpy); jax-pallas-interpret runs the "
                         "fused Pallas DP kernels in interpret mode "
                         "(device columns h2d_lane_uploads/h2d_lane_bytes/"
                         "kernel_dispatches are recorded per row)")
    ap.add_argument("--workers", type=int, default=None,
                    help="rail-sweep thread fan-out (default: "
                         "$PFDNN_WORKERS or serial)")
    ap.add_argument("--no-stack", action="store_true",
                    help="legacy per-subset sweep (stack_subsets=False)")
    args = ap.parse_args()
    configure_compile_cache()

    results = run_sweeps(smoke=args.smoke, backend=args.backend,
                         workers=args.workers, stack=not args.no_stack)
    if args.smoke:
        row = next(iter(results.values()))
        assert row["e_total"] is not None and row["rails"], \
            "smoke sweep produced no schedule"
        if (row["backend"] or "numpy") != "numpy":
            # stacked-kernel parity guard: the jitted/Pallas smoke must
            # reproduce the host sweep bit-for-bit
            (network, frac), = SMOKE_CONFIGS
            ref = schedule_for(network, max_rate(network) * frac,
                               "pfdnn", n_max_rails=2, backend="numpy")
            assert (row["e_total"] == ref.e_total
                    and row["rails"] == list(ref.rails)), \
                "smoke sweep diverged from the numpy backend"
        print("smoke sweep OK")
        return
    if args.record_baseline:
        BASELINE_PATH.write_text(json.dumps(results, indent=1))
        print(f"baseline recorded to {BASELINE_PATH}")
        return

    report: dict = {
        "n_max_rails": N_MAX_RAILS,
        # current rows are best-of-`reps` minima (wall_all_s keeps every
        # sample); the baseline/prev reference walls are single-shot
        # recordings, so speedups carry that asymmetry on noisy hosts
        "methodology": "wall_s = min over reps; references single-shot",
        "current": results,
    }
    if BASELINE_PATH.exists():
        baseline = json.loads(BASELINE_PATH.read_text())
        report["baseline"] = baseline
        report["comparison"] = compare(results, baseline,
                                       against="baseline")
    if PREV_PATH.exists():
        prev = json.loads(PREV_PATH.read_text())
        report["previous"] = prev
        prev_cmp = compare(results, prev, against="previous PR")
        for key, row in prev_cmp.items():
            cmp_row = report.setdefault("comparison", {}).setdefault(
                key, {})
            cmp_row["speedup_vs_prev"] = row["speedup"]
            cmp_row["same_vs_prev"] = (row["same_rails"]
                                       and row["same_energy"])
            for stat in ("dp_calls_delta", "dp_lambdas_delta"):
                if stat in row:
                    cmp_row[f"{stat}_vs_prev"] = row[stat]
    report["smoke_backends"] = smoke_backend_compare()
    report["host"] = host_meta(args.backend)
    pathlib.Path(args.out).write_text(json.dumps(report, indent=1))
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
