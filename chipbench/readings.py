"""The readings the limits of ``compare`` are set from, for one cell, in
one process on the chip:

    python -m chipbench.readings --workload <cell> --seconds <s> \
        --seeds 1 2 ... --control-seeds 101 102 103

One set-up, then for each seed the harness's window of that seed's
traffic and its check, with the program as it is (the lower readings).
Every window also reads the ledger's precision control,
``ledger_f32_rel_err`` (the reference's ledger of each schedule summed
in float32, in the place of the program's).  The ``--control-seeds``
windows follow a set-up of their own with the program's device sweep in
float32 (the jax backend's float64 scope switched off), the step below
the float64 the configurations state.

Prints one JSON line per window.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from chipbench import compare, device, harness, system  # noqa: E402


def _windows(config, mix, seeds, seconds, control) -> None:
    compiler = harness.set_up(config, mix, "jax")
    print(f"set-up done at {time.perf_counter() - T_START!r} s",
          file=sys.stderr, flush=True)
    for seed in seeds:
        window, _, dtypes = harness.drive(
            compiler, config, mix, seed, seconds, "jax",
            log=lambda line: print(line, file=sys.stderr, flush=True))
        verdict = compare.check(window.records, config, seed, dtypes)
        print(json.dumps({
            "seed": seed, "control": control,
            "requests": len(window.records),
            "numbers": {k: v["value"]
                        for k, v in verdict["numbers"].items()},
            "ledger_f32_rel_err": verdict["ledger_f32_rel_err"],
            "faults": verdict["faults"][:5]}), flush=True)
    compiler.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m chipbench.readings")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    spec = harness.load_spec()
    cell = harness.find_cell(spec, args.workload)
    system.import_program()
    system.configure_compile_cache()
    device.open_chips(cell["chips"])
    config = harness.load_json("configs", cell["config"])
    mix = harness.load_json("traffic", cell["traffic"])
    if args.seeds:
        _windows(config, mix, args.seeds, args.seconds, None)
    if args.control_seeds:
        with system.no_device_x64():
            _windows(config, mix, args.control_seeds, args.seconds,
                     "device_f32")
    return 0


if __name__ == "__main__":
    sys.exit(main())
