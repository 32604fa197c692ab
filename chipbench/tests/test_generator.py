"""The traffic mixes are pure functions of the seed."""

import pytest

from chipbench import generator, harness

# every configuration under every mix the files define
CELLS = [(c.stem, t.stem)
         for c in sorted((harness.HERE / "configs").glob("*.json"))
         for t in sorted((harness.HERE / "traffic").glob("*.json"))]


def _mix(config, traffic):
    return (harness.load_json("configs", config),
            harness.load_json("traffic", traffic))


@pytest.mark.parametrize("config,traffic", CELLS)
def test_same_seed_same_stream(config, traffic):
    cfg, mix = _mix(config, traffic)
    seed = 2**31 + 977
    a = generator.Traffic(cfg, mix, seed).take(60)
    b = generator.Traffic(cfg, mix, seed).take(60)
    assert a == b
    assert a != generator.Traffic(cfg, mix, seed + 1).take(60)


@pytest.mark.parametrize("config,traffic", CELLS)
def test_every_seed_gets_the_same_work_in_another_order(config, traffic):
    """Each cycle visits every network once; over the cycles a network
    walks all of its (resolution, rate stratum) variants, and every seed
    visits them in the same sequence (only the rates differ)."""
    cfg, mix = _mix(config, traffic)
    nets = list(cfg["networks"])
    strata = mix.get("strata", 1)
    sizes = mix.get("input_hw") or [None]
    n_cycles = strata * len(sizes)
    lo, hi = mix["rate_frac"]
    for seed in (1, 2**33 + 5):
        reqs = generator.Traffic(cfg, mix, seed).take(len(nets) * n_cycles)
        for c in range(n_cycles):
            cycle = reqs[c * len(nets):(c + 1) * len(nets)]
            assert sorted(r.network for r in cycle) == sorted(nets)
        for net in nets:
            mine = [r for r in reqs if r.network == net]
            cells = {(r.input_hw, int((r.rate_frac - lo) / (hi - lo)
                                      * strata)) for r in mine}
            assert len(cells) == n_cycles
            assert all(lo <= r.rate_frac < hi for r in mine)
    visits = [[(r.network, r.input_hw, int((r.rate_frac - lo) / (hi - lo)
                                           * strata))
               for r in generator.Traffic(cfg, mix, seed).take(40)]
              for seed in (1, 2**33 + 5)]
    assert visits[0] == visits[1]


def test_warm_resolve_never_repeats_a_point():
    cfg, mix = _mix("edge40nm-5rail", "warm-resolve")
    reqs = generator.Traffic(cfg, mix, 12345).take(400)
    points = {(r.network, r.input_hw, r.rate_hz) for r in reqs}
    warm = {(r.network, r.input_hw, r.rate_hz)
            for r in generator.Traffic(cfg, mix, 12345).warmup()}
    assert len(points) == len(reqs) and not points & warm
