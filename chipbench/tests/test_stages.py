"""The stage reduction (chipbench.stages) on small recorded traces, and
the program's spans and lane-fill counter in a traced rehearsal."""

import gzip
import json
import pathlib

import pytest

from chipbench import stages, system, trace
from chipbench.tests.conftest import run_tiny

DATA = pathlib.Path(__file__).parent / "data"


@pytest.fixture(scope="module")
def staged():
    from jax.profiler import ProfileData

    text = (DATA / "stages_trace.pbtxt").read_text()
    return trace.flatten(ProfileData.from_text_proto(text))


def test_programs_are_unions_of_module_intervals(staged):
    """The DP program's %while.3 holds two fusions: its ops sum to
    4.9 µs in 3 µs of program; the program counts each instant once."""
    assert stages.reduce(staged)["programs"] == pytest.approx({
        "jit_pfdnn_dp_lanes": 5000e-9, "jit_pfdnn_kbest_lanes": 3800e-9})
    ops = dict(trace.reduce(staged)["device_ops"])
    assert ops["jit_pfdnn_dp_lanes(11):while.3"] == pytest.approx(4900e-9)
    assert sum(ops.values()) == pytest.approx(10700e-9)


def test_span_seconds_per_name(staged):
    assert stages.reduce(staged)["spans"] == pytest.approx({
        "pfdnn.compile_many": 16800e-9, "pfdnn.sweep": 9600e-9,
        "pfdnn.round": 9600e-9, "pfdnn.round.dispatch": 1000e-9,
        "pfdnn.round.moves": 400e-9, "pfdnn.round.barrier": 6800e-9,
        "pfdnn.round.eval": 1100e-9, "pfdnn.round.admit": 600e-9})


def test_idle_goes_to_the_innermost_program_span(staged):
    """Every idle instant goes to the innermost pfdnn.* span over it;
    the Python frames ($...) around and inside the spans are passed
    over, and a gap split by span edges is split with it."""
    idle = stages.reduce(staged)["idle_by_stage"]
    assert idle == pytest.approx({
        stages.NO_SPAN: 1200e-9, "pfdnn.compile_many": 5200e-9,
        "pfdnn.round": 100e-9, "pfdnn.round.dispatch": 600e-9,
        "pfdnn.round.moves": 400e-9, "pfdnn.round.eval": 1100e-9,
        "pfdnn.round.admit": 600e-9, stages.BETWEEN: 2000e-9})
    red = trace.reduce(staged)
    assert sum(idle.values()) == pytest.approx(
        red["window_s"] - red["busy_s"])


def test_program_name():
    assert stages.program_name("jit_pfdnn_dp_lanes(123)") == \
        "jit_pfdnn_dp_lanes"
    assert stages.program_name("jit_impl") == "jit_impl"


def test_nothing_to_read_gives_none(staged):
    no_requests = [p for p in staged if p["name"] != "/host:CPU"]
    assert stages.reduce(no_requests) is None
    assert stages.span_seconds(no_requests) == {}
    no_device = [p for p in staged if not p["name"].startswith("/dev")]
    assert stages.reduce(no_device) is None
    assert stages.span_seconds(no_device) == \
        stages.reduce(staged)["spans"]


def test_innermost_pieces_of_nested_spans():
    pieces = stages._innermost([(0, 10, "a"), (2, 5, "b"), (6, 8, "c"),
                                (12, 14, "d")])
    assert pieces == [(0, 2, "a"), (2, 5, "b"), (5, 6, "a"), (6, 8, "c"),
                      (8, 10, "a"), (12, 14, "d")]


@pytest.fixture
def device_lanes(monkeypatch):
    """The jax backend as on the chip: every DP and k-best round goes to
    the lane programs instead of the host's numpy kernels."""
    system.import_program()
    from repro.core.backend import JaxBackend

    monkeypatch.setattr(JaxBackend, "_JIT_MIN_WORK", 0)
    monkeypatch.setattr(JaxBackend, "_KBEST_JIT_MIN_WORK", 0)


def test_traced_rehearsal_reads_spans_and_lane_fill(tiny_bench,
                                                    device_lanes,
                                                    monkeypatch):
    """The tiny traced run: the program's round spans are in the trace
    and positive, and the lane-fill share is reported."""
    planes = []
    load = trace.load

    def keep(trace_dir):
        planes.extend(load(trace_dir))
        return planes

    monkeypatch.setattr(trace, "load", keep)
    spec, base = tiny_bench
    spec["per_layer"].append(dict(
        name="lane_fill_share", unit="%", better="higher",
        source="program_counter", layer="backend",
        moves="schedules_per_s", workloads=["tiny.tiny-warm"]))
    out = run_tiny(spec, base, "tiny.tiny-warm", traced=True,
                   backend="jax")
    assert out["correct"], out["checks"]
    assert 0 < out["metrics"]["lane_fill_share"]["value"] <= 100
    spans = stages.span_seconds(planes)
    for name in ("pfdnn.round.barrier", "pfdnn.round.eval",
                 "pfdnn.round.moves", "pfdnn.round.admit"):
        assert spans[name] > 0, name
    assert spans["pfdnn.compile_many"] > spans["pfdnn.sweep"] > \
        spans["pfdnn.round.eval"]


def test_recorded_chip_slice():
    """40 ms of a mobilenetv3-small re-solve traced on one TPU v5e with
    the program's spans and named programs (the request thread with the
    Python tracer's frames, the device's ops and programs), clipped to
    the slice; its reduction as read on the chip."""
    with gzip.open(DATA / "chip_stages_trace.json.gz", "rt") as f:
        planes = json.load(f)
    red, st = trace.reduce(planes), stages.reduce(planes)
    assert red["window_s"] == pytest.approx(0.04)
    assert red["busy_s"] == pytest.approx(0.01323456)
    assert st["programs"] == pytest.approx({
        "jit_pfdnn_dp_lanes": 0.002530079,
        "jit_pfdnn_kbest_lanes": 0.010707232})
    assert sum(st["programs"].values()) == pytest.approx(
        red["busy_s"], rel=1e-3)
    assert st["spans"] == pytest.approx({
        "pfdnn.compile_many": 0.04, "pfdnn.sweep": 0.04,
        "pfdnn.round": 0.037950231, "pfdnn.round.dispatch": 0.012543818,
        "pfdnn.round.barrier": 0.014287291, "pfdnn.round.eval": 0.00421206,
        "pfdnn.round.admit": 0.006729269, "pfdnn.round.moves": 0.00110774})
    idle = st["idle_by_stage"]
    assert idle == pytest.approx({
        "pfdnn.sweep": 0.002028149, "pfdnn.round": 0.00013616,
        "pfdnn.round.dispatch": 0.003597543,
        "pfdnn.round.barrier": 0.00916905, "pfdnn.round.eval": 0.004178123,
        "pfdnn.round.admit": 0.006663606, "pfdnn.round.moves": 0.000992809})
    assert sum(idle.values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    # every device op ran inside a named program, none in a jit_impl
    device = planes[0]
    module = trace._module_of(device)
    names = {module(s) for _, s, _ in device["lines"]["XLA Ops"]}
    assert names and all(n.startswith("jit_pfdnn_") for n in names)
    assert not any("jit_impl" in op for op, _ in red["device_ops"])
