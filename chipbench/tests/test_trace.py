"""The trace reduction on small recorded traces."""

import gzip
import json
import pathlib

import pytest

from chipbench import trace

DATA = pathlib.Path(__file__).parent / "data"


@pytest.fixture(scope="module")
def synthetic():
    from jax.profiler import ProfileData

    text = (DATA / "synthetic_trace.pbtxt").read_text()
    return trace.flatten(ProfileData.from_text_proto(text))


def test_busy_and_idle_share(synthetic):
    red = trace.reduce(synthetic)
    assert red["window_s"] == pytest.approx(20000e-9)
    assert red["busy_s"] == pytest.approx(8650e-9)
    assert red["n_devices"] == 1          # TPU:1 ran nothing
    assert 1 - red["busy_s"] / red["window_s"] == pytest.approx(0.5675)


def test_device_ops_by_program(synthetic):
    ops = dict(trace.reduce(synthetic)["device_ops"])
    assert ops == pytest.approx({"jit_kbest:while.7": 3900e-9,
                                 "jit_dp:while.3": 3750e-9,
                                 "jit_dp:fusion.1": 1500e-9})


def test_gaps_put_down_to_the_innermost_host_span(synthetic):
    gaps = dict(trace.reduce(synthetic)["idle_gaps"])
    assert gaps == pytest.approx({
        "host, inside a request (no finer span)": 2300e-9,
        "TransferFromDevice": 5000e-9,
        "between requests": 4050e-9})
    assert sum(gaps.values()) == pytest.approx(20000e-9 - 8650e-9)


def test_nothing_to_read_gives_none(synthetic):
    no_requests = [p for p in synthetic if p["name"] != "/host:CPU"]
    assert trace.reduce(no_requests) is None
    no_device = [p for p in synthetic if not p["name"].startswith("/dev")]
    assert trace.reduce(no_device) is None


def test_recorded_chip_trace():
    """40 ms of squeezenet1.1 re-solves traced on one TPU v5e (the host
    thread with the request span, the device's ops and programs),
    clipped to the slice; its reduction as read on the chip."""
    with gzip.open(DATA / "chip_trace.json.gz", "rt") as f:
        planes = json.load(f)
    red = trace.reduce(planes)
    assert red["n_devices"] == 1
    assert red["window_s"] == pytest.approx(0.04)
    assert red["busy_s"] == pytest.approx(0.010926030000000002)
    top_op, top_s = red["device_ops"][0]
    assert top_op.endswith("%while.27") and top_s == pytest.approx(
        0.004032351)
    gaps = dict(red["idle_gaps"])
    assert gaps["$array.py:631 _value"] == pytest.approx(0.008550529)
    idle = red["window_s"] - red["busy_s"]
    top_gaps = sum(s for _, s in red["idle_gaps"])
    assert 0.99 * idle < top_gaps <= idle
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10


def test_flatten_reads_a_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2).sum())
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.REQUEST_SPAN):
        f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    planes = trace.load(str(tmp_path))
    host = trace._host_line(planes)
    assert [n for n, _, _ in host].count(trace.REQUEST_SPAN) == 1
