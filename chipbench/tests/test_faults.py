"""``correct`` comes out false when the timed path is broken underneath,
once for each fault a compile cell can have, and under the precision
control.  Each test drives a whole rehearsal run (set-up, window,
check) of the throwaway one-network cell, off the chip."""

import dataclasses

import numpy as np
import pytest

from chipbench import compare, generator, system
from chipbench.tests.conftest import run_tiny


@pytest.fixture(autouse=True)
def _program():
    system.import_program()


def _break(monkeypatch, alter):
    """Make the answer of every window request on the jax backend
    ``alter(request, schedule)``; the set-up's warm-up requests (index
    -1) and the host backend the check compiles on stay sound."""
    compile_ = system.Compiler.compile

    def broken(self, req):
        out = compile_(self, req)
        if req.index < 0 or self.backend != "jax":
            return out
        return alter(self, req, out)

    monkeypatch.setattr(system.Compiler, "compile", broken)


def _relabel(sched, req, voltages):
    """``sched`` with other voltages, its ledger re-derived for them."""
    acc = generator.accelerator({})
    led = compare.reference_ledger(
        dataclasses.replace(sched, layer_voltages=voltages), req, acc)
    return dataclasses.replace(
        sched, layer_voltages=voltages, t_infer=led["t_infer"],
        e_op=led["e_op"], e_trans=led["e_trans"], e_idle=led["e_idle"],
        e_total=led["e_total"], n_rail_switches=led["n_rail_switches"],
        z_active_idle=led["z_active_idle"])


def test_sound_run_is_correct(tiny_bench):
    spec, base = tiny_bench
    assert run_tiny(spec, base, "tiny.tiny-warm", backend="jax")["correct"]


def test_an_altered_voltage_fails(tiny_bench, monkeypatch):
    """One layer's compute domain moved to another rail, the recorded
    ledger left as it was."""
    def alter(self, req, sched):
        volts = list(sched.layer_voltages)
        other = [r for r in sched.rails if r != volts[0][0]]
        if not other:
            return dataclasses.replace(sched, e_total=sched.e_total * 1.01)
        volts[0] = (other[0],) + tuple(volts[0][1:])
        return dataclasses.replace(sched, layer_voltages=volts)

    _break(monkeypatch, alter)
    spec, base = tiny_bench
    out = run_tiny(spec, base, "tiny.tiny-warm", backend="jax")
    assert not out["correct"]
    assert out["checks"]["ledger_rel_err"]["value"] > \
        out["checks"]["ledger_rel_err"]["limit"]


def test_a_worse_schedule_with_a_true_ledger_fails(tiny_bench, monkeypatch):
    """Every domain on one rail at the top of the menu: a schedule that
    holds its deadline and whose ledger is true, but that spends more
    energy than the host backend's answer."""
    def alter(self, req, sched):
        top = max(generator.accelerator({}).levels())
        volts = [tuple(top if v else v for v in row)
                 for row in sched.layer_voltages]
        return _relabel(dataclasses.replace(sched, rails=(top,)), req,
                        volts)

    _break(monkeypatch, alter)
    spec, base = tiny_bench
    out = run_tiny(spec, base, "tiny.tiny-warm", backend="jax")
    assert not out["correct"]
    assert out["checks"]["violations"]["value"] == 0
    assert out["checks"]["energy_gap"]["value"] > \
        out["checks"]["energy_gap"]["limit"]


def test_a_broken_guarantee_fails(tiny_bench, monkeypatch):
    """A layer driven from a voltage that is not one of the rails."""
    def alter(self, req, sched):
        menu = generator.accelerator({}).levels()
        spare = [v for v in menu if v not in sched.rails][0]
        volts = list(sched.layer_voltages)
        volts[-1] = (spare,) + tuple(volts[-1][1:])
        return _relabel(sched, req, volts)

    _break(monkeypatch, alter)
    spec, base = tiny_bench
    out = run_tiny(spec, base, "tiny.tiny-warm", backend="jax")
    assert not out["correct"] and out["checks"]["violations"]["value"] > 0


def test_an_answer_that_never_comes_fails(tiny_bench, monkeypatch):
    def alter(self, req, sched):
        raise RuntimeError("compile lost")

    _break(monkeypatch, alter)
    spec, base = tiny_bench
    out = run_tiny(spec, base, "tiny.tiny-warm", backend="jax")
    assert not out["correct"] and out["failed"] == out["attempted"] > 0


def test_precision_control_ledger_in_float32_fails(tiny_bench, monkeypatch):
    """The control: the reference's ledger accumulated in float32, in
    the place of the program's float64 one."""
    def alter(self, req, sched):
        led = compare.reference_ledger(sched, req, generator.accelerator({}),
                                       dtype=np.float32)
        return dataclasses.replace(
            sched, **{k: led[k] for k in ("t_infer", "e_op", "e_trans",
                                          "e_idle", "e_total")})

    _break(monkeypatch, alter)
    spec, base = tiny_bench
    out = run_tiny(spec, base, "tiny.tiny-warm", backend="jax")
    assert not out["correct"]
    assert out["checks"]["ledger_rel_err"]["value"] > \
        out["checks"]["ledger_rel_err"]["limit"]


def test_precision_control_device_sweep_in_float32_fails(tiny_bench,
                                                         monkeypatch):
    """The control: the program's device sweep in float32 (its float64
    scope switched off), every kernel call sent to the device as on the
    chip."""
    from repro.core.backend import JaxBackend

    monkeypatch.setattr(JaxBackend, "_JIT_MIN_WORK", 0)
    monkeypatch.setattr(JaxBackend, "_KBEST_JIT_MIN_WORK", 0)
    spec, base = tiny_bench
    with system.no_device_x64():
        out = run_tiny(spec, base, "tiny.tiny-warm", backend="jax")
    assert not out["correct"]
    assert out["checks"]["float_bits_short"]["value"] == 32
