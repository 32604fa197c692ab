"""One run of one cell, driven by data.

``BENCHMARK.json`` names the cells.  A cell's configuration is
``configs/<config>.json``, its traffic mix ``traffic/<traffic>.json``
(read by :mod:`chipbench.generator`), and each metric is read by
``metrics/<metric name>.py`` (a module with ``read(run)`` that returns
the number, or None where the run holds nothing to read); a metric
``<reader>.<suffix>`` without a file of its own is read by
``metrics/<reader>.py``.  A new cell, configuration, mix or metric is a
new file and an entry; nothing here changes.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time

from chipbench import compare, generator, loop, system, trace

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
#: the traced run profiles the requests that start in the first this many
#: seconds of its window (a second of the sweep is ~1M trace events)
TRACE_SECONDS = 3.0


def load_spec(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_json(kind: str, name: str, base: pathlib.Path = HERE) -> dict:
    path = base / kind / f"{name}.json"
    if not path.is_file():
        raise KeyError(f"no {kind[:-1] if kind.endswith('s') else kind} "
                       f"file {path}")
    return json.loads(path.read_text())


def reader(name: str, base: pathlib.Path = HERE):
    """The ``read`` function of ``metrics/<name>.py``, or of the reader
    the name starts with (``device_idle_share.cold`` is read by
    ``device_idle_share.py``)."""
    path = base / "metrics" / f"{name}.py"
    if not path.is_file():
        path = base / "metrics" / f"{name.split('.')[0]}.py"
    if not path.is_file():
        raise KeyError(f"no reader {path} for metric {name!r}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; one of "
                   f"{[c['name'] for c in spec['workloads']]}")


def metrics_of(spec: dict, cell: str, traced: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics, or
    with the trace on its per-layer metrics."""
    group = spec["per_layer" if traced else "end_to_end"]
    return [m for m in group
            if cell in m.get("workloads", [cell])]


@dataclasses.dataclass
class Run:
    """What a reader reads."""

    cell: dict
    window: loop.Window
    setup_s: float
    counters: dict            # backend counters over the window
    trace: dict | None        # trace.reduce() of the traced requests
    traced: int = 0           # requests of the window under the profiler

    @property
    def schedules(self) -> list:
        return _schedules(self.window.records)

    @property
    def traced_schedules(self) -> list:
        return _schedules(self.window.records[:self.traced])


def _schedules(records) -> list:
    return [r.result for r in records
            if r.error is None and compare._is_schedule(r.result)]


class _Profiler:
    """Profiles the first requests of the window, each in a request
    span, and stops once :data:`TRACE_SECONDS` have passed."""

    def __init__(self, trace_dir: str):
        import jax

        self.jax, self.dir, self.traced, self.t0 = jax, trace_dir, 0, None
        jax.profiler.start_trace(trace_dir)

    @contextlib.contextmanager
    def annotate(self, req):
        if self.t0 is None:
            self.t0 = time.perf_counter()
        if self.dir is None:
            yield
            return
        with self.jax.profiler.TraceAnnotation(trace.REQUEST_SPAN,
                                               network=req.label):
            yield
        self.traced += 1
        if time.perf_counter() - self.t0 >= TRACE_SECONDS:
            self.stop()

    def stop(self) -> None:
        if self.dir is not None:
            self.jax.profiler.stop_trace()
            self.dir = None


def set_up(config: dict, mix: dict, backend: str) -> "system.Compiler":
    """The program for one cell, warmed up on the mix's set-up requests
    (the same for every seed)."""
    compiler = system.Compiler(config, backend)
    for req in generator.Traffic(config, mix, 0).warmup():
        result = compiler.compile(req)
        if not compare._is_schedule(result):
            raise RuntimeError(f"warm-up {req.label} gave {result!r}")
    return compiler


def drive(compiler, config: dict, mix: dict, seed: int, seconds: float,
          backend: str, events=None, annotate=None,
          log=lambda line: None):
    """The measured window on ``seed``'s traffic; returns it with the
    backend counters over it and the device arrays' dtypes after it.
    Logs what the window built or uploaded that set-up should have."""
    before = system.backend_counters(backend)
    compiles_before = events.snapshot() if events else (0, 0, 0.0)
    window = loop.drive(compiler.compile,
                        iter(generator.Traffic(config, mix, seed)),
                        seconds, annotate=annotate)
    after = system.backend_counters(backend)
    compiles = events.snapshot() if events else (0, 0, 0.0)
    programs, loaded, build_s = (a - b for a, b in zip(compiles,
                                                       compiles_before))
    counters = {k: after[k] - before.get(k, 0) for k in after}
    log(f"window: {len(window.records)} requests in {window.seconds!r} s")
    uploads = counters.get("h2d_lane_uploads", 0)
    if programs or uploads:
        log(f"WARNING: set-up left work inside the window: {programs} "
            f"programs built in {build_s!r} s (XLA compiles: "
            f"{programs - loaded}, loaded from the persistent cache: "
            f"{loaded}), {uploads} lanes uploaded")
    return window, counters, system.device_dtypes()


def run_cell(spec: dict, cell_name: str, seed: int, seconds: float,
             traced: bool, *, t_start: float, devices=None, events=None,
             backend: str = "jax", base: pathlib.Path = HERE,
             log=lambda line: print(line, file=sys.stderr, flush=True)
             ) -> dict:
    """Set up, warm up, drive the window, check, and return the result
    line's object.  ``devices`` are the chips JAX opened (None in a
    rehearsal off the chip)."""
    from chipbench import device

    cell = find_cell(spec, cell_name)
    config = load_json("configs", cell["config"], base)
    mix = load_json("traffic", cell["traffic"], base)
    readers = [(m, reader(m["name"], base))
               for m in metrics_of(spec, cell_name, traced)]

    compiler = set_up(config, mix, backend)
    setup_s = time.perf_counter() - t_start
    log(f"setup_s: {setup_s!r}")

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
        if traced else None
    profiler = _Profiler(trace_dir) if traced else None
    window, counters, dtypes = drive(
        compiler, config, mix, seed, seconds, backend, events,
        annotate=profiler and profiler.annotate, log=log)
    if profiler:
        profiler.stop()

    dev = device.describe(devices) if devices else {"platform": None,
                                                    "kind": None,
                                                    "count": 0}
    dev["memory_peak_bytes"] = device.memory_peak_bytes(devices) \
        if devices else None
    compiler.close()
    del compiler
    gc.collect()

    reduced = None
    if traced:
        reduced = trace.reduce(trace.load(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        if reduced is not None:
            dev["busy_s"] = reduced["busy_s"]
            dev["window_s"] = reduced["window_s"]

    run = Run(cell, window, setup_s, counters, reduced,
              profiler.traced if profiler else 0)
    metrics = {}
    for m, read in readers:
        value = read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    tic = time.perf_counter()
    verdict = compare.check(window.records, config, seed, dtypes)
    log(f"reference check: {time.perf_counter() - tic!r} s")
    failed = sum(1 for r in window.records
                 if r.error is not None or not compare._is_schedule(r.result))
    log(f"precision control (reference ledger in float32): "
        f"ledger_rel_err {verdict['ledger_f32_rel_err']!r}")
    for fault in verdict["faults"]:
        log(f"FAULT {fault}")
    for name, num in verdict["numbers"].items():
        log(f"check {name}: {num['value']!r} (limit {num['limit']!r})")
    out = {"correct": verdict["correct"] and failed == 0,
           "attempted": len(window.records), "failed": failed,
           "metrics": metrics, "device": dev}
    if reduced is not None:
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                     for k, v in verdict["numbers"].items()}
    return out
