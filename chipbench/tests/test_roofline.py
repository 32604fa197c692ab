"""The lane programs' memory bound: bytes per dispatch shape, the peak
table, and the reader on a recorded chip slice."""

import gzip
import json
import pathlib
import types

import pytest

from chipbench import harness, roofline, trace

DATA = pathlib.Path(__file__).parent / "data"


def test_bytes_of_one_hand_counted_dispatch():
    """A rung-4 k-best dispatch (k 10, 4 μ columns) on a mobilenetv3-
    small lane store: L 54, S_pad 128, one 128 × 128 block."""
    lane = (54 * 128 * 8 * 2        # t_op, e_op
            + 54 * 128              # valid
            + 53 * 4                # block_of
            + 53 * 128 * 4 * 2      # rsel, csel
            + 1 * 128 * 128 * 8 * 2)  # t_blk, e_blk
    assert lane == 434_132
    want = 4 * (lane + 8) + 4 * 4 * 8 + 4 * 4 * (10 * 54 + 1) * 8
    assert roofline.dispatch_bytes("kbest", 10, 54, 128, 1, 128, 4, 4) \
        == want == 1_805_936
    dp = 4 * (lane + 8) + 2 * 4 * 32 * 8 + 4 * 32 * 54 * 8
    assert roofline.dispatch_bytes("dp", 0, 54, 128, 1, 128, 4, 32) == dp
    with pytest.raises(ValueError):
        roofline.dispatch_bytes("costs", 0, 54, 128, 1, 128, 4, 4)


def test_peak_table_refuses_an_unknown_device():
    assert roofline.peak_hbm_bytes_per_s("TPU v5 lite") == 819e9
    with pytest.raises(KeyError, match="TPU v4"):
        roofline.peak_hbm_bytes_per_s("TPU v4")


def _sched(*rows):
    return types.SimpleNamespace(solver_stats={"lane_dispatches": list(rows)})


def _row(kind, k, rung, kp, n):
    return dict(kind=kind, k=k, L=54, S_pad=128, NB=1, SB=128, rung=rung,
                Kp=kp, n=n)


def test_reader_on_the_recorded_chip_slice(monkeypatch):
    """The 40 ms v5e slice ran 4 DP and 3 k-best lane programs of a
    mobilenetv3-small re-solve; counted at the widest shape they could
    have had (rung 16, all λ columns), the share stays far below 100%."""
    import jax

    with gzip.open(DATA / "chip_stages_trace.json.gz", "rt") as f:
        red = trace.reduce(json.load(f))
    run = types.SimpleNamespace(
        trace=red, traced_schedules=[_sched(_row("dp", 0, 16, 32, 4),
                                            _row("kbest", 10, 16, 4, 3))])
    monkeypatch.setattr(jax, "devices", lambda: [types.SimpleNamespace(
        device_kind="TPU v5 lite")])
    share = harness.reader("lane_hbm_share")(run)
    moved = 4 * roofline.dispatch_bytes("dp", 0, 54, 128, 1, 128, 16, 32) \
        + 3 * roofline.dispatch_bytes("kbest", 10, 54, 128, 1, 128, 16, 4)
    assert share == pytest.approx(100 * moved / (819e9 * red["busy_s"]))
    assert 0 < share <= 100
    # a program that counts no dispatches (the parent) reads nothing
    run.traced_schedules = [types.SimpleNamespace(solver_stats={})]
    assert harness.reader("lane_hbm_share")(run) is None
    # an unknown device with a trace is an error, not a guess
    run.traced_schedules = [_sched(_row("dp", 0, 1, 32, 1))]
    monkeypatch.setattr(jax, "devices", lambda: [types.SimpleNamespace(
        device_kind="TPU v9")])
    with pytest.raises(KeyError):
        harness.reader("lane_hbm_share")(run)
