"""Run one cell of the benchmark once, on the chip this process holds.

    python -m chipbench --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Sets up, warms up, drives the cell's traffic for ``--seconds``, checks
every schedule of the window, and prints one JSON object as the last
line of stdout (the numbers compared, with their limits, are also the
last lines of stderr).  ``--trace 1`` runs the window under the profiler
and reports the cell's per-layer metrics instead of its end-to-end ones.
Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for, or when the compiler's sources are not in the
checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m chipbench")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import device, harness, system

    try:
        spec = harness.load_spec()
        cell = harness.find_cell(spec, args.workload)
        system.import_program()
    except (OSError, KeyError, ImportError) as exc:
        print(f"chipbench: {exc}", file=sys.stderr)
        return 2
    system.configure_compile_cache()
    events = device.CompileEvents()
    try:
        devices = device.open_chips(cell["chips"])
    except device.NoChip as exc:
        print(f"chipbench: {exc}", file=sys.stderr)
        return 1
    print(f"device: {device.describe(devices)}", file=sys.stderr,
          flush=True)
    out = harness.run_cell(spec, args.workload, args.seed, args.seconds,
                           bool(args.trace), t_start=T_START,
                           devices=devices, events=events)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
