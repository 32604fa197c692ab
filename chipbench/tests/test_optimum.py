"""The reference's bound on the optimum, against every schedule of a
problem small enough to enumerate."""

import itertools

import pytest

from chipbench.reference import networks, optimum, physics

ACC = physics.Accelerator(v_min=0.9, v_max=1.0, v_step=0.05)
N_RAILS = 2


@pytest.fixture
def three_layers(monkeypatch):
    """A network of squeezenet1.1's first two layers around a weightless
    pool, so that the RRAM domain may be gated under it."""
    first = networks.network("squeezenet1.1", 224)
    layers = [first[0], networks.pool("pool", 112, 112, 64, 3), first[1]]
    assert [x.weight_bytes == 0 for x in layers] == [False, True, False]
    real = networks.network
    monkeypatch.setattr(optimum.nets, "network",
                        lambda name, hw=None: layers if name == "three"
                        else real(name, hw))
    return layers


def _every_schedule(layers):
    """(t_infer, energy of the layers and switches) of every assignment
    of voltages under every rail subset."""
    for rails in itertools.chain.from_iterable(
            itertools.combinations(ACC.levels(), k)
            for k in range(1, N_RAILS + 1)):
        per_layer = [list(itertools.product(
            rails, rails,
            rails + ((physics.V_GATED,) if not x.weight_bytes else ())))
            for x in layers]
        for volts in itertools.product(*per_layer):
            yield physics.ledger(layers, ACC, volts, 1.0)["t_infer"], volts


def _optimum(layers, t_max):
    return min(physics.ledger(layers, ACC, volts, t_max)["e_total"]
               for t, volts in _every_schedule(layers) if t <= t_max)


@pytest.mark.parametrize("slack", [1.0001, 1.05, 1.3, 3.0, 100.0])
def test_bound_is_below_the_optimum(three_layers, slack):
    t_fast = min(t for t, _ in _every_schedule(three_layers))
    t_max = t_fast * slack
    best = _optimum(three_layers, t_max)
    bound = optimum.lower_bound("three", 224, 1.0 / t_max, N_RAILS, ACC)
    assert bound <= best * (1 + 1e-12)
    # with slack the dual is the optimum (to the few 1e-8 by which a
    # negative time weight undercharges the transitions that switch no
    # rail); at a tight deadline three layers leave a duality gap of ~2%
    assert bound >= best * (1 - (1e-6 if slack >= 3.0 else 0.02))
    # a schedule in hand as the cutoff never raises the bound above it
    assert optimum.lower_bound("three", 224, 1.0 / t_max, N_RAILS, ACC,
                               cutoff=best) <= best * (1 + 1e-12)


def test_an_infeasible_deadline_bounds_far_above_every_schedule(
        three_layers):
    t_fast = min(t for t, _ in _every_schedule(three_layers))
    worst = max(physics.ledger(three_layers, ACC, volts, t)["e_total"]
                for t, volts in _every_schedule(three_layers))
    bound = optimum.lower_bound("three", 224, 1.0 / (0.5 * t_fast),
                                N_RAILS, ACC)
    assert bound > 1e3 * worst
