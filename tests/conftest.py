"""Shared fixtures. NOTE: no XLA_FLAGS here — smoke tests must see the
single real CPU device; multi-device sharding tests spawn subprocesses
with their own flags (tests/test_sharding.py)."""

import numpy as np
import pytest

from repro.core.problem import IdleModel, ScheduleProblem, StateCost
from repro.hw.dvfs import TransitionModel
from repro.hw.edge40nm import EDGE40NM_DEFAULT
from repro.models.edge_cnn import edge_network
from repro.perfmodel import characterize_network


def max_rate(name: str, acc=EDGE40NM_DEFAULT) -> float:
    """Max feasible inference rate = 1 / latency with all domains at
    V_max (the fastest any schedule can run).  Golden keys and operating
    points are derived from this — keep it the single test-side copy."""
    costs = characterize_network(edge_network(name), acc)
    fs = [acc.dvfs(d).freq(acc.v_max) for d in range(3)]
    t = sum(max(cy / f for cy, f in zip(c.cycles, fs)) for c in costs)
    return 1.0 / t


def random_problem(rng: np.random.Generator, *, n_layers: int,
                   n_states: int, t_max_scale: float = 1.0,
                   allow_sleep: bool = True) -> ScheduleProblem:
    """Random-but-valid layered problem for property tests."""
    layers = []
    volt_menu = [0.7, 0.8, 0.9, 1.0, 1.1]
    for _ in range(n_layers):
        states = []
        for _ in range(n_states):
            v = tuple(rng.choice(volt_menu, size=3))
            t = float(rng.uniform(1e-5, 1e-3))
            e = float(rng.uniform(1e-7, 1e-4))
            states.append(StateCost(v, t, e))
        layers.append(states)
    min_t = sum(min(s.t_op for s in states) for states in layers)
    max_t = sum(max(s.t_op for s in states) for states in layers)
    t_max = float(min_t + (max_t - min_t) * rng.uniform(0.1, 1.2))
    t_max *= t_max_scale
    idle = IdleModel(p_idle=float(rng.uniform(1e-4, 1e-2)),
                     p_sleep=float(rng.uniform(1e-6, 1e-4)),
                     e_sleep_wake=float(rng.uniform(1e-9, 1e-7)),
                     t_sleep_wake=1e-6,
                     allow_sleep=allow_sleep)
    return ScheduleProblem(
        layer_states=layers, t_max=t_max, idle=idle,
        transition_model=TransitionModel(v_min=0.7, v_max=1.1))


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True, scope="module")
def _collect_module_garbage():
    """Collect each test module's garbage when it ends.  The workers run
    many files in one process, and a later file's check of the live
    device arrays (chipbench's float-precision check reads every array
    JAX still holds) must not see float32 arrays that an earlier
    module's serving models left in reference cycles."""
    yield
    import gc

    gc.collect()
