"""Pallas kernels for the subset-stacked sweep's inner reductions.

Three kernels mirror the backend's stacked entry points
(:meth:`~repro.core.backend.JaxBackend.dp_multi_stacked`,
``kbest_multi_stacked``, ``path_costs_stacked``), fusing the
argmin/argsort reduction with the follow-up gather per lane:

  - :func:`dp_multi_stacked_pallas` — the batched multi-λ DP.  Grid is
    the lane axis; each grid step owns one lane's ``[L, S]`` /
    ``[L-1, S, S]`` blocks and runs the full layer recurrence with the
    parent gather fused (``take_along_axis`` from the argmin result —
    the same bits as a second ``min`` reduction at O(K·S) cost).
  - :func:`kbest_multi_stacked_pallas` — the fused multi-μ k-best
    frontier, one lane per grid step.  Tie order is the stable
    ``(value, flat index)`` sort of ``jnp.argsort`` — identical to the
    numpy kernel's ``kind="stable"`` order.
  - :func:`path_components_pallas` — the gather side of stacked path
    evaluation.  Gridless (one instance over the whole lane store):
    the per-grid-step block copies interpret mode would make of the
    full ``[B, L-1, S, S]`` tensors cost more than the gather itself.
    It returns PER-LAYER components, not sums — the caller reduces on
    the host with ``np.sum`` so warm results are bit-identical to the
    numpy backend's pairwise summation.

The kernels take dense ``[B, L-1, S, S]`` transition tensors: the
backend expands its compact lane blocks to them on the host
(``repro.core.backend.dense_edges``), which is why these kernels are a
CPU interpret vehicle only.

Bit-identity contract (pinned by tests/test_pallas_sweep.py): the
layer loops are unrolled over the static L, node costs mask invalid
states to ``inf`` *after* weighting, and all reductions run over the
full padded S — pad states are ``inf`` and index-last, so
first-occurrence ``argmin`` picks the same state as the numpy kernels'
sliced reductions.  IEEE addition is commutative, so the weighted-edge
accumulation order matches the scan path bit for bit.

All wrappers take ``interpret`` as a static jit arg: ``interpret=True``
runs everywhere (the CPU tier-1 mode), ``False`` compiles for the
accelerator backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


# ------------------------------------------------------------------ dp

def _dp_kernel(t_op_ref, e_op_ref, valid_ref, t_trans_ref, e_trans_ref,
               w_e_ref, w_t_ref, out_ref, *, n_layers: int):
    L = n_layers
    t_op = t_op_ref[0]                                    # [L, S]
    e_op = e_op_ref[0]
    valid = valid_ref[0]
    w_e = w_e_ref[0]                                      # [K]
    w_t = w_t_ref[0]
    node = (w_e[None, :, None] * e_op[:, None, :]
            + w_t[None, :, None] * t_op[:, None, :])      # [L, K, S]
    node = jnp.where(valid[:, None, :], node, jnp.inf)
    cost = node[0]
    parents = []
    for i in range(1, L):
        tot = cost[:, :, None] + (
            w_e[:, None, None] * e_trans_ref[0, i - 1]
            + w_t[:, None, None] * t_trans_ref[0, i - 1])
        parent = jnp.argmin(tot, axis=1)                  # [K, Sn]
        # gather the min through the argmin — same bits as jnp.min
        cost = jnp.take_along_axis(
            tot, parent[:, None, :], axis=1)[:, 0, :] + node[i]
        parents.append(parent)
    s = jnp.argmin(cost, axis=1)                          # [K]
    states = [s]
    for i in range(L - 2, -1, -1):
        s = jnp.take_along_axis(parents[i], s[:, None], axis=1)[:, 0]
        states.append(s)
    states.reverse()
    out_ref[0] = jnp.stack(states, axis=1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dp_multi_stacked_pallas(t_op, e_op, valid, t_trans, e_trans,
                            w_e, w_t, *, interpret: bool = True):
    """Stacked multi-λ DP: tensors ``[B, L, S]`` / ``[B, L-1, S, S]``,
    weights ``[B, K]`` → best-path states ``[B, K, L]`` int32."""
    B, L, S = t_op.shape
    K = w_e.shape[1]
    if L == 1:
        # no transition blocks to tile — the plain-jnp argmin is the
        # whole kernel (matches the scan path's L == 1 special case)
        node = (w_e[:, :, None] * e_op[:, None, 0, :]
                + w_t[:, :, None] * t_op[:, None, 0, :])
        node = jnp.where(valid[:, None, 0, :], node, jnp.inf)
        return jnp.argmin(node, axis=2)[:, :, None].astype(jnp.int32)
    lane3 = pl.BlockSpec((1, L, S), lambda b: (b, 0, 0))
    lane4 = pl.BlockSpec((1, L - 1, S, S), lambda b: (b, 0, 0, 0))
    lane_w = pl.BlockSpec((1, K), lambda b: (b, 0))
    return pl.pallas_call(
        functools.partial(_dp_kernel, n_layers=L),
        grid=(B,),
        in_specs=[lane3, lane3, lane3, lane4, lane4, lane_w, lane_w],
        out_specs=pl.BlockSpec((1, K, L), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, L), jnp.int32),
        interpret=interpret,
    )(t_op, e_op, valid, t_trans, e_trans, w_e, w_t)


# -------------------------------------------------------------- k-best

def _kbest_kernel(t_op_ref, e_op_ref, valid_ref, t_trans_ref,
                  e_trans_ref, mus_ref, paths_ref, counts_ref, *,
                  n_layers: int, k: int):
    L = n_layers
    t_op = t_op_ref[0]                                    # [L, S]
    e_op = e_op_ref[0]
    valid = valid_ref[0]
    mus = mus_ref[0]                                      # [K]
    K, S = mus.shape[0], t_op.shape[1]
    node = e_op[:, None, :] + mus[None, :, None] * t_op[:, None, :]
    node = jnp.where(valid[:, None, :], node, jnp.inf)    # [L, K, S]
    costs = jnp.full((K, S, k), jnp.inf, dtype=t_op.dtype)
    costs = costs.at[:, :, 0].set(node[0])
    back = []
    for i in range(1, L):
        edge = (e_trans_ref[0, i - 1][None]
                + mus[:, None, None] * t_trans_ref[0, i - 1][None])
        cand = (costs[:, :, :, None]
                + edge[:, :, None, :]).reshape(K, S * k, S)
        order = jnp.argsort(cand, axis=1)[:, :k, :]       # stable
        vals = jnp.take_along_axis(cand, order, axis=1)
        costs = vals.transpose(0, 2, 1) + node[i][:, :, None]
        back.append((order // k, order % k))
    flat = costs.reshape(K, S * k)
    order = jnp.argsort(flat, axis=1)[:, :k]
    counts_ref[0] = jnp.minimum(
        k, jnp.isfinite(flat).sum(axis=1)).astype(jnp.int32)
    s, r = order // k, order % k
    qi = jnp.arange(K)[:, None]
    states = [s]
    for i in range(L - 2, -1, -1):
        ps, pr = back[i]
        s, r = ps[qi, r, s], pr[qi, r, s]
        states.append(s)
    states.reverse()
    paths_ref[0] = jnp.stack(states, axis=2).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def kbest_multi_stacked_pallas(t_op, e_op, valid, t_trans, e_trans,
                               mus, *, k: int,
                               interpret: bool = True):
    """Stacked multi-μ k-best frontier → ``(paths [B, K, k, L] int32,
    counts [B, K] int32)``; rows past ``counts[b, q]`` carry no
    meaning (they backtrack inf-cost frontier slots)."""
    B, L, S = t_op.shape
    K = mus.shape[1]
    if L == 1:
        node = (e_op[:, None, 0, :]
                + mus[:, :, None] * t_op[:, None, 0, :])
        node = jnp.where(valid[:, None, 0, :], node, jnp.inf)
        costs = jnp.full((B, K, S, k), jnp.inf, dtype=t_op.dtype)
        costs = costs.at[:, :, :, 0].set(node)
        flat = costs.reshape(B, K, S * k)
        order = jnp.argsort(flat, axis=2)[:, :, :k]
        counts = jnp.minimum(k, jnp.isfinite(flat).sum(axis=2))
        return (order[:, :, :, None] // k).astype(jnp.int32), \
            counts.astype(jnp.int32)
    lane3 = pl.BlockSpec((1, L, S), lambda b: (b, 0, 0))
    lane4 = pl.BlockSpec((1, L - 1, S, S), lambda b: (b, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_kbest_kernel, n_layers=L, k=k),
        grid=(B,),
        in_specs=[lane3, lane3, lane3, lane4, lane4,
                  pl.BlockSpec((1, K), lambda b: (b, 0))],
        out_specs=[
            pl.BlockSpec((1, K, k, L), lambda b: (b, 0, 0, 0)),
            pl.BlockSpec((1, K), lambda b: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, K, k, L), jnp.int32),
            jax.ShapeDtypeStruct((B, K), jnp.int32),
        ],
        interpret=interpret,
    )(t_op, e_op, valid, t_trans, e_trans, mus)


# --------------------------------------------------------- path gather

def _gather_kernel(lanes_ref, paths_ref, t_op_ref, e_op_ref,
                   t_trans_ref, e_trans_ref, switch_ref,
                   t_out, e_out, tt_out, et_out, sw_out):
    ln = lanes_ref[...][:, None]                          # [P, 1]
    pa = paths_ref[...]                                   # [P, L]
    L = pa.shape[1]
    li = jnp.arange(L)[None, :]
    t_out[...] = t_op_ref[...][ln, li, pa]
    e_out[...] = e_op_ref[...][ln, li, pa]
    lt = jnp.arange(L - 1)[None, :]
    a, b = pa[:, :-1], pa[:, 1:]
    tt_out[...] = t_trans_ref[...][ln, lt, a, b]
    et_out[...] = e_trans_ref[...][ln, lt, a, b]
    sw_out[...] = switch_ref[...][ln, lt, a, b]


@functools.partial(jax.jit, static_argnames=("interpret",))
def path_components_pallas(lanes, paths, t_op, e_op, t_trans, e_trans,
                           switch, *, interpret: bool = True):
    """Per-layer cost components of P paths on lanes of one stack:
    ``lanes [P]``, ``paths [P, L]`` → ``(t_op [P, L], e_op [P, L],
    t_trans [P, L-1], e_trans [P, L-1], switch [P, L-1])``.

    The caller sums on the host (``np.sum`` over the layer axis) so
    the reduced values are bit-identical to the numpy backend's
    gather-and-sum.  Requires L >= 2 (the backend handles L == 1
    without a kernel — there are no transition components to gather).
    """
    P, L = paths.shape
    return pl.pallas_call(
        _gather_kernel,
        out_shape=[
            jax.ShapeDtypeStruct((P, L), t_op.dtype),
            jax.ShapeDtypeStruct((P, L), e_op.dtype),
            jax.ShapeDtypeStruct((P, L - 1), t_trans.dtype),
            jax.ShapeDtypeStruct((P, L - 1), e_trans.dtype),
            jax.ShapeDtypeStruct((P, L - 1), switch.dtype),
        ],
        interpret=interpret,
    )(lanes, paths, t_op, e_op, t_trans, e_trans, switch)
