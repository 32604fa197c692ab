"""The compiler's own profiler spans (repro.core.spans) and the jax
backend's lane-fill counters."""

import glob
import os
import subprocess
import sys

import numpy as np
import pytest
from conftest import max_rate, random_problem

from repro.core import spans
from repro.core.backend import BucketStack, build_padded, get_backend

jax = pytest.importorskip("jax")

ROUND_PHASES = (spans.ROUND_DISPATCH, spans.ROUND_MOVES,
                spans.ROUND_BARRIER, spans.ROUND_EVAL, spans.ROUND_ADMIT)


def test_span_names_are_the_program_prefix():
    names = [v for k, v in vars(spans).items() if k.isupper()
             and isinstance(v, str)]
    assert len(names) == len(set(names)) == 11
    assert all(n.startswith("pfdnn.") for n in names)


def test_spans_do_not_load_jax():
    """A numpy-only process stays free of jax: the helper imports
    nothing and hands out one shared no-op context."""
    code = ("import sys\n"
            "from repro.core.spans import span, ROUND\n"
            "a, b = span(ROUND, tasks=3), span('pfdnn.x')\n"
            "with a:\n"
            "    pass\n"
            "assert a is b\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + [p for p in [env.get("PYTHONPATH")] if p])
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr


def test_span_is_a_trace_annotation_once_jax_is_loaded():
    assert isinstance(spans.span(spans.SWEEP),
                      jax.profiler.TraceAnnotation)


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in line.events]
            if any(n == spans.COMPILE_MANY for n, _, _ in events):
                return events
    raise AssertionError("no pfdnn.compile_many span in the trace")


def _inside(child, parents):
    _, s, e = child
    return any(ps <= s and e <= pe for _, ps, pe in parents)


def test_spans_nest_in_a_traced_compile(tmp_path, monkeypatch):
    """One jax-backend compile under the profiler: every round phase
    sits in a round, every round in the sweep, the sweep in the batch,
    and there is one round span per stacked round."""
    from repro.core import OrchestratorConfig
    from repro.models.edge_cnn import edge_network
    from repro.service import CompileRequest, CompileService, MinEnergy

    bk = get_backend("jax")
    # as on the chip: the lane programs run on the device mirror
    monkeypatch.setattr(type(bk), "_JIT_MIN_WORK", 0)
    monkeypatch.setattr(type(bk), "_KBEST_JIT_MIN_WORK", 0)
    cfg = OrchestratorConfig(policy="pfdnn", n_max_rails=2,
                             backend="jax")
    net = "squeezenet1.1"
    req = CompileRequest(edge_network(net), cfg=cfg, network=net,
                         goal=MinEnergy(rate_hz=0.8 * max_rate(net)))
    with CompileService(use_schedule_cache=False) as svc:
        svc.compile_many([req])              # compiles the programs
        jax.profiler.start_trace(str(tmp_path))
        try:
            sched, = svc.compile_many([req])
        finally:
            jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    by = {}
    for ev in events:
        by.setdefault(ev[0], []).append(ev)
    batch, = by[spans.COMPILE_MANY]
    sweep, = by[spans.SWEEP]
    assert _inside(sweep, [batch])
    assert all(_inside(c, [batch]) for c in by[spans.CONTEXT])
    assert all(_inside(e, [batch]) and not _inside(e, [sweep])
               for e in by[spans.EMIT])
    rounds = by[spans.ROUND]
    assert len(rounds) == sched.solver_stats["stacked_rounds"] > 0
    assert all(_inside(r, [sweep]) for r in rounds)
    for phase in ROUND_PHASES:
        assert all(_inside(e, rounds) for e in by.get(phase, []))
    for phase in (spans.ROUND_DISPATCH, spans.ROUND_BARRIER,
                  spans.ROUND_EVAL, spans.ROUND_ADMIT):
        assert len(by[phase]) == len(rounds), phase
    assert by[spans.ROUND_MOVES], "refinement scored no moves"
    assert all(_inside(e, by[spans.ROUND_DISPATCH])
               for e in by[spans.ROUND_MOVES])
    # warm: the lanes are resident, nothing is uploaded
    assert spans.LANES_UPLOAD not in by


def _lane_store(rng, n_lanes=3, n_layers=4, n_states=4):
    store = BucketStack(n_layers, n_states)
    for i in range(n_lanes):
        store.add(("lane", i), build_padded(random_problem(
            rng, n_layers=n_layers, n_states=n_states)))
    return store


@pytest.mark.parametrize("kind", ["dp", "kbest"])
def test_lane_slots_count_the_padding(monkeypatch, rng, kind):
    """A 3-lane, 3-column dispatch runs on a 4 × 4 padded slab: it adds
    Bp·Kp = 16 slots, B·K = 9 of them used."""
    bk = get_backend("jax")
    monkeypatch.setattr(bk, "_cpu", False)
    store = _lane_store(rng)
    lanes = [0, 1, 2]
    before = dict(bk.io_stats)
    if kind == "dp":
        bk.dp_multi_lanes(store, lanes, rng.random((3, 3)),
                          rng.random((3, 3)))
    else:
        bk.kbest_multi_lanes(store, lanes, rng.random((3, 3)) * 10.0, 4)
    delta = {k: bk.io_stats[k] - before[k] for k in before}
    assert delta["kernel_dispatches"] == 1
    assert delta["lane_slots"] == 4 * 4
    assert delta["lane_slots_used"] == 3 * 3


def test_lane_programs_have_stable_names(monkeypatch, rng):
    """The device programs are named, so a trace reads
    ``jit_pfdnn_dp_lanes`` and not ``jit_impl``."""
    bk = get_backend("jax")
    monkeypatch.setattr(bk, "_cpu", False)
    store = _lane_store(rng)
    bk.dp_multi_lanes(store, [0, 1], rng.random((2, 2)),
                      rng.random((2, 2)))
    bk.kbest_multi_lanes(store, [0, 1], rng.random((2, 2)), 3)
    bk.kbest_multi_lanes(store, [0, 1], rng.random((2, 2)), 5)
    assert bk._lanes_fn("dp").__name__ == "pfdnn_dp_lanes"
    for k in (3, 5):
        assert bk._lanes_fn("kbest", k).__name__ == "pfdnn_kbest_lanes"
    assert bk._set_block.__name__ == "pfdnn_lane_block_set"
    m = bk._mirror(store)
    idx = np.zeros(2, dtype=np.int64)
    with bk._x64():
        text = bk._lanes_fn("dp").lower(
            *m.arrays[:bk._N_DP], idx, np.ones((2, 2)),
            np.ones((2, 2))).as_text()
    assert "jit_pfdnn_dp_lanes" in text and "jit_impl" not in text


def test_lane_upload_span_names_its_lanes_and_bytes(tmp_path, monkeypatch,
                                                    rng):
    """A cold mirror sync is one ``pfdnn.lanes.upload`` span whose
    arguments are the lanes it uploads and their bytes, the same bytes
    ``io_stats["h2d_lane_bytes"]`` counts."""
    from jax.profiler import ProfileData

    bk = get_backend("jax")
    monkeypatch.setattr(bk, "_cpu", False)
    store = _lane_store(rng)
    before = bk.io_stats["h2d_lane_bytes"]
    jax.profiler.start_trace(str(tmp_path))
    try:
        bk._mirror(store)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    ups = [dict(e.stats) for plane in ProfileData.from_file(path).planes
           for line in plane.lines for e in line.events
           if e.name == spans.LANES_UPLOAD]
    assert ups == [{"lanes": 3,
                    "bytes": bk.io_stats["h2d_lane_bytes"] - before}]
    assert ups[0]["bytes"] > 0
