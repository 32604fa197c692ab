"""A plain lower bound on the optimum of a MinEnergy request.

The problem (arXiv 2603.23882 §4): pick at most ``n_max_rails`` supply
levels from the menu, then one of them per domain and layer (the RRAM
domain may instead be gated under a weightless layer), so that the
inference time with its switch latencies fits the period and the
energy of the layers, the switches and the terminal idle interval is
least.  Any schedule's rails lie in some subset of exactly
``min(n_max_rails, levels)`` levels, so the optimum is the least over
those subsets.

For one subset the bound is the Lagrangian dual of the deadline.  The
idle energy of a slack ``s`` is at least ``min(p_idle s, E_wake +
p_sleep s)`` (the wake-latency rule only ever raises it), so the
optimum is at least the lesser of two problems with a linear idle term;
for each, every ``lambda >= 0`` gives

    g(lambda) = min over paths [E + (lambda - beta) T]
                + alpha + beta t_max - lambda t_max  <=  optimum,

and the bound is the best ``g`` found by bisecting ``lambda`` on the
sign of ``T(path) - t_max``.  The inner minimum is a dynamic programme
over the layers; a transition's energy is a sum over domains, so the
minimum over the previous layer's states is taken one domain at a time,
charged the rail-switch latency, and completed by the few transitions
that switch no rail (none, a wake, or a gating) at their own latency.
Where ``lambda < beta`` the rail-switch branch undercharges those few,
which only lowers the bound.

Everything here is numpy over :mod:`chipbench.reference.physics`; it
imports nothing of the compiler under test.
"""

from __future__ import annotations

import functools
import itertools

import numpy as np

from chipbench.reference import networks as nets
from chipbench.reference import physics

#: bisection steps on log(lambda) over a bracket that spans 1e16 around
#: the scale E/t_max: 36 halvings leave a ratio of 1 + 5e-10 between its
#: ends, far below the gaps the bound is held to
BISECT_STEPS = 36
BRACKET = 1e8


@functools.lru_cache(maxsize=32)
def _tables(network: str, input_hw: int, acc: physics.Accelerator):
    """Per-layer node time and energy over the full grid
    ``[layer, v_compute, v_feeder, v_rram]`` (the last RRAM index is
    gated; inf energy where a state is not allowed), the per-domain
    switch energy ``[a, b]`` and latency class of each, and the
    idle constants."""
    layers = nets.network(network, input_hw)
    levels = acc.levels()
    n = len(levels)
    volts_r = list(levels) + [physics.V_GATED]
    banks = physics.Banks.place(layers, acc)
    L = len(layers)
    t_node = np.zeros((L, n, n, n + 1))
    e_node = np.full((L, n, n, n + 1), np.inf)
    for i, spec in enumerate(layers):
        cycles, dyn = physics.characterize(spec, acc)
        for (c, vc), (f, vf), (r, vr) in itertools.product(
                enumerate(levels), enumerate(levels), enumerate(volts_r)):
            if vr == physics.V_GATED and spec.weight_bytes:
                continue
            t, e = physics.layer_op(cycles, dyn, i, acc, banks,
                                    (vc, vf, vr), True)
            t_node[i, c, f, r] = t
            e_node[i, c, f, r] = e
    e_sw = np.array([[acc.switch_energy(a, b) for b in volts_r]
                     for a in volts_r])
    p_idle = (acc.leak_compute + acc.leak_feeder
              + acc.leak_rram_bank) * (1.0 + acc.idle_residual_dyn)
    p_sleep = (acc.leak_compute + acc.leak_feeder
               + acc.leak_rram_bank * banks.n_banks) \
        * acc.sleep_retention_frac
    # (alpha, beta) of the two linear idle pieces: active, deep sleep
    idle = ((0.0, p_idle), (acc.sleep_wake_energy, p_sleep))
    return t_node, e_node, e_sw, idle


def _subsets(n_levels: int, n_max_rails: int) -> np.ndarray:
    m = min(n_max_rails, n_levels)
    return np.array(list(itertools.combinations(range(n_levels), m)))


def _min_along(cost, time, add, axis):
    """``min`` over ``axis`` of ``cost + add`` (``add`` broadcast), with
    the time of the winner; the minimised axis is replaced in place by
    the new one ``add`` carries after it."""
    tot = cost + add
    k = np.argmin(tot, axis=axis)
    best = np.take_along_axis(tot, np.expand_dims(k, axis), axis)
    t = np.take_along_axis(np.broadcast_to(time, tot.shape),
                           np.expand_dims(k, axis), axis)
    return np.squeeze(best, axis), np.squeeze(t, axis)


def _dual_dp(t_node, e_node, e_c, e_r, mu, t_rail, t_wake):
    """``min over paths of E + mu T`` and that path's ``T``, for a batch
    of subsets: ``t_node``/``e_node`` ``[N, L, m, m, m+1]`` (last RRAM
    state gated), ``e_c`` ``[N, m, m]`` and ``e_r`` ``[N, m+1, m+1]``
    switch energies, ``mu`` ``[N]``."""
    mu5 = mu[:, None, None, None]
    cost = e_node[:, 0] + mu5 * t_node[:, 0]
    time = t_node[:, 0]
    g = cost.shape[-1] - 1                   # the gated RRAM index
    for i in range(1, t_node.shape[1]):
        # any transition, charged a rail switch: one domain at a time
        # cost[n, a_c, a_f, a_r] -> [n, b_c, a_f, a_r]
        c1, t1 = _min_along(cost[:, :, None], time[:, :, None],
                            e_c[:, :, :, None, None], 1)
        c1, t1 = _min_along(c1[:, :, :, None], t1[:, :, :, None],
                            e_c[:, None, :, :, None], 2)
        c1, t1 = _min_along(c1[:, :, :, :, None], t1[:, :, :, :, None],
                            e_r[:, None, None, :, :], 3)
        c1 = c1 + mu5 * t_rail
        t1 = t1 + t_rail
        # no rail switched: stay (T 0), wake the RRAM (t_wake), gate it
        # (T 0)
        cands = [(c1, t1), (cost, time)]
        wake_c = np.full_like(cost, np.inf)
        wake_t = np.zeros_like(time)
        wake_c[..., :g] = cost[..., g:] + e_r[:, None, None, g, :g] \
            + mu5 * t_wake
        wake_t[..., :g] = time[..., g:] + t_wake
        cands.append((wake_c, wake_t))
        sleep_c = np.full_like(cost, np.inf)
        sleep_t = np.zeros_like(time)
        s_c, s_t = _min_along(cost[..., :g], time[..., :g],
                              e_r[:, None, None, :g, g], 3)
        sleep_c[..., g] = s_c
        sleep_t[..., g] = s_t
        cands.append((sleep_c, sleep_t))
        cs = np.stack([c for c, _ in cands])
        ts = np.stack([t for _, t in cands])
        k = np.argmin(cs, axis=0)
        cost = np.take_along_axis(cs, k[None], 0)[0] \
            + e_node[:, i] + mu5 * t_node[:, i]
        time = np.take_along_axis(ts, k[None], 0)[0] + t_node[:, i]
    flat_c = cost.reshape(len(mu), -1)
    k = np.argmin(flat_c, axis=1)
    return flat_c[np.arange(len(mu)), k], \
        time.reshape(len(mu), -1)[np.arange(len(mu)), k]


def lower_bound(network: str, input_hw: int, rate_hz: float,
                n_max_rails: int, acc: physics.Accelerator,
                cutoff: float = np.inf) -> float:
    """A lower bound on the least energy of any schedule of
    ``network`` at ``input_hw`` that meets the period ``1 / rate_hz``
    with at most ``n_max_rails`` rails (see the module docstring).

    A subset whose bound already reaches ``cutoff`` (the energy of a
    schedule in hand) is no longer refined: it cannot bring the least
    bound under ``cutoff``."""
    t_node, e_node, e_sw, idle = _tables(network, input_hw, acc)
    t_max = 1.0 / rate_hz
    n = t_node.shape[1]
    subs = _subsets(n, n_max_rails)                        # [S, m]
    rr = np.concatenate([subs, np.full((len(subs), 1), n)], axis=1)
    # batch = (subset, idle piece)
    subs2 = np.repeat(subs, 2, axis=0)
    rr2 = np.repeat(rr, 2, axis=0)
    alpha = np.tile([a for a, _ in idle], len(subs))
    beta = np.tile([b for _, b in idle], len(subs))
    ix_c = subs2[:, :, None, None]
    ix_f = subs2[:, None, :, None]
    ix_r = rr2[:, None, None, :]
    tn = t_node[:, ix_c, ix_f, ix_r].transpose(1, 0, 2, 3, 4)
    en = e_node[:, ix_c, ix_f, ix_r].transpose(1, 0, 2, 3, 4)
    e_c = e_sw[subs2[:, :, None], subs2[:, None, :]]
    e_r = e_sw[rr2[:, :, None], rr2[:, None, :]]

    def g(lam, ix):
        val, t = _dual_dp(tn[ix], en[ix], e_c[ix], e_r[ix],
                          lam - beta[ix], acc.t_rail, acc.t_wake)
        return val + alpha[ix] + beta[ix] * t_max - lam * t_max, t

    every = np.arange(len(beta))
    best, t0 = g(np.zeros(len(beta)), every)
    # lambda = 0 is the maximiser where its path meets the deadline
    open_ = every[(t0 > t_max) & (best < cutoff)]
    scale = float(np.min(best[np.isfinite(best)])) / t_max
    lo = np.full(len(beta), np.log(scale / BRACKET))
    hi = np.full(len(beta), np.log(scale * BRACKET))
    for _ in range(BISECT_STEPS):
        if not len(open_):
            break
        mid = 0.5 * (lo[open_] + hi[open_])
        val, t = g(np.exp(mid), open_)
        best[open_] = np.maximum(best[open_], val)
        slow = t > t_max
        lo[open_] = np.where(slow, mid, lo[open_])
        hi[open_] = np.where(slow, hi[open_], mid)
        open_ = open_[best[open_] < cutoff]
    return float(best.min())
