"""The one traffic generator: turns a configuration, a traffic mix (a
data file under ``traffic/``) and a seed into the request stream.

Every seed gets the same work in the same order: the stream is made of
cycles that visit each of the configuration's networks once, in the
configuration's order, and each network walks its variants (input
resolution x rate stratum, strata ascending).  Only the rate fraction is
drawn from the seed, uniformly inside the stratum, so no point repeats,
and a window that holds a few requests holds the same work on every
seed.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Iterator

import numpy as np

from chipbench.reference import networks as nets
from chipbench.reference import physics


@dataclasses.dataclass(frozen=True)
class Request:
    """One compile request as the benchmark sends it."""

    index: int
    network: str
    input_hw: int
    rate_frac: float
    rate_hz: float
    #: the compiler's incumbent cuts on (False: a set-up request that
    #: solves every rail subset, so that every lane is resident)
    cuts: bool = True

    @property
    def label(self) -> str:
        return f"{self.network}@{self.input_hw}"

    def layers(self) -> list[nets.Layer]:
        return nets.network(self.network, self.input_hw)


def accelerator(config: dict) -> physics.Accelerator:
    return physics.Accelerator(**config.get("accelerator", {}))


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([seed % (1 << 64), *stream]))


def variants(config: dict, mix: dict) -> list[tuple[str, int]]:
    """Every (network, input resolution) the mix sends."""
    sizes = mix.get("input_hw")
    return [(name, hw) for name, published in config["networks"].items()
            for hw in (sizes or [published])]


class Traffic:
    """The seeded request stream of one cell (see module docstring)."""

    def __init__(self, config: dict, mix: dict, seed: int):
        self.config, self.mix, self.seed = config, mix, seed
        self.acc = accelerator(config)
        self._max_rate = {}
        for name, hw in variants(config, mix):
            self._max_rate[(name, hw)] = physics.max_rate(
                nets.network(name, hw), self.acc)

    def max_rate(self, network: str, input_hw: int) -> float:
        return self._max_rate[(network, input_hw)]

    def _request(self, index: int, network: str, hw: int,
                 frac: float, cuts: bool = True) -> Request:
        return Request(index, network, hw, float(frac),
                       float(frac * self.max_rate(network, hw)), cuts)

    def warmup(self) -> list[Request]:
        """The set-up requests, at fixed points the stream never draws:
        for every variant, first one at each ``warmup_all_subsets``
        fraction with the incumbent cuts off, then one at each
        ``warmup_rate_frac``."""
        return [self._request(-1, name, hw, frac, cuts)
                for name, hw in variants(self.config, self.mix)
                for key, cuts in (("warmup_all_subsets", False),
                                  ("warmup_rate_frac", True))
                for frac in self.mix.get(key, [])]

    def _points(self, net_i: int, network: str) -> Iterator[tuple]:
        """A network's endless walk over its (resolution, stratum)
        variants, with the rate drawn inside each stratum."""
        sizes = self.mix.get("input_hw") or \
            [self.config["networks"][network]]
        n_strata = int(self.mix.get("strata", 1))
        lo, hi = self.mix["rate_frac"]
        combos = [(hw, s) for hw in sizes for s in range(n_strata)]
        rng = _rng(self.seed, 1, net_i)
        while True:
            for hw, s in combos:
                u = rng.random()
                yield hw, lo + (hi - lo) * (s + u) / n_strata

    def __iter__(self) -> Iterator[Request]:
        names = list(self.config["networks"])
        walks = [self._points(i, n) for i, n in enumerate(names)]
        index = itertools.count()
        while True:
            for i in range(len(names)):
                hw, frac = next(walks[i])
                yield self._request(next(index), names[i], hw, frac)

    def take(self, n: int) -> list[Request]:
        return list(itertools.islice(iter(self), n))
