"""Compact lane stores: each distinct transition block once per lane.

A lane keeps its transition blocks once (``[NB, SB, SB]``) with, per
layer boundary, the block it reads and its states' rows and columns in
it; the DP and k-best scans read each boundary's block at their step.
These tests hold the compact layout to the problem's own transition
matrices and every kernel on it to the same kernel on a one-block-per-
boundary layout of the same numbers, bit for bit, on five-rail subset
problems of a seeded random network whose weightless layers carry the
gated RRAM state (125 and 150 states a layer, S_pad 256).
"""

import numpy as np
import pytest

from repro.core.backend import (
    BucketStack,
    JaxBackend,
    PaddedArrays,
    build_padded,
    get_backend,
    repad,
    stack_padded,
)
from repro.core.context import CompilationContext
from repro.core.pruning import prune_problem
from repro.core.refinement import move_scores
from repro.perfmodel import LayerSpec

jax = pytest.importorskip("jax")

FIVE_RAILS = (0.9, 1.0, 1.1, 1.2, 1.3)


def random_network(seed: int, n_layers: int) -> list[LayerSpec]:
    """Seeded convolutions and fully connected layers, with weightless
    pooling and eltwise layers between them."""
    rng = np.random.default_rng(seed)
    specs = []
    for i in range(n_layers):
        kind = rng.choice(["conv", "fc", "pool", "eltwise"],
                          p=[0.4, 0.2, 0.2, 0.2])
        c_in, c_out = (int(x) for x in rng.integers(8, 64, 2))
        p_out = int(rng.integers(16, 256))
        weights = c_in * c_out * 9 if kind in ("conv", "fc") else 0
        specs.append(LayerSpec(
            name=f"l{i}", kind=str(kind), macs=p_out * max(weights, c_out),
            weight_bytes=weights, act_in_bytes=p_out * c_in,
            act_out_bytes=p_out * c_out, p_out=p_out, c_out=c_out,
            c_in=c_in, kernel=3 if kind == "conv" else 1))
    return specs


def subset_problems(seed: int, n_layers: int = 10, prune: bool = False,
                    n: int = 6):
    """Array-backed (master-sliced) problems of ``n`` rail subsets of a
    random network, the five-rail subset first."""
    ctx = CompilationContext(random_network(seed, n_layers), 50.0)
    subsets = [FIVE_RAILS] + [FIVE_RAILS[:j] + FIVE_RAILS[j + 1:]
                              for j in range(n - 1)]
    out = []
    for rails in subsets:
        p = ctx.problem_for(rails, gating=True, allow_sleep=True,
                            materialize_states=False)
        out.append(prune_problem(p)[0] if prune else p)
    return out


def per_boundary_blocks(problem) -> PaddedArrays:
    """The same problem with one block per boundary, read from the
    problem's own transition matrices (a dense per-boundary layout's
    numbers, as blocks)."""
    padded = build_padded(problem)
    L, S = padded.n_layers, padded.s_pad
    blk = [np.zeros((L - 1, S, S)), np.zeros((L - 1, S, S)),
           np.zeros((L - 1, S, S), dtype=np.int64)]
    for i in range(L - 1):
        a, b = problem.sizes[i], problem.sizes[i + 1]
        tt, et = problem.transition_arrays(i)
        for arr, src in zip(blk, (tt, et, problem.switch_arrays(i))):
            arr[i, :a, :b] = src
    states = np.tile(np.arange(S, dtype=np.int32), (L - 1, 1))
    return PaddedArrays(
        t_op=padded.t_op, e_op=padded.e_op, valid=padded.valid,
        t_blk=blk[0], e_blk=blk[1], sw_blk=blk[2],
        block_of=np.arange(L - 1, dtype=np.int32), rsel=states,
        csel=states, sizes=padded.sizes)


@pytest.mark.parametrize("prune", [False, True])
def test_blocks_hold_the_problems_transitions(prune):
    problems = subset_problems(7, prune=prune)
    assert prune or set(problems[0].sizes) == {125, 150}
    for problem in problems:
        padded = build_padded(problem)
        for i in range(problem.n_layers - 1):
            a, b = problem.sizes[i], problem.sizes[i + 1]
            tt, et = padded.edges(i)
            t_ref, e_ref = problem.transition_arrays(i)
            np.testing.assert_array_equal(tt[:a, :b], t_ref)
            np.testing.assert_array_equal(et[:a, :b], e_ref)
            sw = padded.sw_blk[padded.block_of[i],
                               padded.rsel[i, :a, None],
                               padded.csel[i, None, :b]]
            np.testing.assert_array_equal(sw, problem.switch_arrays(i))


def test_boundaries_with_equal_voltage_tables_share_a_block():
    """Unpruned, a lane holds one block per distinct pair of adjacent
    voltage tables: weight → weight, weight → weightless, weightless →
    weight (and weightless → weightless where two meet)."""
    problem = subset_problems(7, n_layers=40, n=1)[0]
    padded = build_padded(problem)
    pairs = {(problem._volts[i].tobytes(), problem._volts[i + 1].tobytes())
             for i in range(problem.n_layers - 1)}
    assert padded.s_pad == padded.block_size == 256
    assert padded.n_blocks == len(pairs) <= 4
    assert max(problem.sizes) == 150 and min(problem.sizes) == 125


def test_lane_bytes_do_not_grow_with_depth():
    """A lane's block arrays are the same at 10 and at 60 layers; only
    its per-layer rows (op costs, validity, block indices) grow."""
    store_bytes = {}
    for n_layers in (10, 60):
        problem = subset_problems(3, n_layers=n_layers, n=1)[0]
        padded = build_padded(problem)
        store = BucketStack(padded.n_layers, padded.s_pad)
        store.add("lane", padded)
        blocks = sum(getattr(store, nm)[:1].nbytes
                     for nm in ("_t_blk", "_e_blk", "_sw_blk"))
        rows = sum(getattr(store, nm)[:1].nbytes
                   for nm in ("_t_op", "_e_op", "_valid", "_block_of",
                              "_rsel", "_csel", "_sizes"))
        per_layer = padded.s_pad * (8 + 8 + 1 + 4 + 4) + 4 + 8
        store_bytes[n_layers] = (blocks, rows, per_layer)
    (b10, r10, per), (b60, r60, _) = store_bytes[10], store_bytes[60]
    assert b10 == b60
    assert r60 - r10 == 50 * per


def _stores(prune: bool):
    """(compact store, one-block-per-boundary store, lane problems) of
    the same lanes, at S_pad 256."""
    problems = subset_problems(11, prune=prune)
    compact = BucketStack(problems[0].n_layers, 256)
    plain = BucketStack(problems[0].n_layers, 256)
    for j, p in enumerate(problems):
        compact.add(j, repad(build_padded(p), 256))
        plain.add(j, repad(per_boundary_blocks(p), 256))
    assert compact.n_blocks <= 4 < plain.n_blocks
    return compact, plain, problems


@pytest.fixture(scope="module", params=[False, True],
                ids=["unpruned", "pruned"])
def stores(request):
    return _stores(request.param)


@pytest.fixture
def device_path(monkeypatch):
    """The jax backend as on the chip: every DP and k-best dispatch runs
    the lane programs on the store's device mirror."""
    jb = get_backend("jax")
    monkeypatch.setattr(jb, "_cpu", False)
    return jb


@pytest.mark.parametrize("lanes", [[0], [1, 2, 3], [5, 4, 3, 2, 1]],
                         ids=["rung1", "rung4", "rung16"])
def test_compact_lanes_match_numpy_bit_for_bit(stores, device_path,
                                               lanes):
    compact, plain, _ = stores
    nb = get_backend("numpy")
    rng = np.random.default_rng(len(lanes))
    w_e = rng.uniform(0.1, 1.0, (len(lanes), 3))
    w_t = rng.uniform(1e-3, 1e3, (len(lanes), 3))
    ref = nb.dp_multi_stacked(device_path._host_member_stack(plain, lanes),
                              w_e, w_t)
    np.testing.assert_array_equal(
        nb.dp_multi_stacked(device_path._host_member_stack(compact, lanes),
                            w_e, w_t), ref)
    np.testing.assert_array_equal(
        device_path.dp_multi_lanes(compact, lanes, w_e, w_t), ref)
    mus = rng.uniform(1e-3, 1e2, (len(lanes), 2))
    ref_p, ref_c = nb.kbest_multi_stacked(
        device_path._host_member_stack(plain, lanes), mus, 3)
    got_p, got_c = device_path.kbest_multi_lanes(compact, lanes, mus, 3)
    np.testing.assert_array_equal(got_c, ref_c)
    for b in range(len(lanes)):
        for q in range(mus.shape[1]):
            n = ref_c[b, q]
            np.testing.assert_array_equal(got_p[b, q, :n], ref_p[b, q, :n])


def test_path_costs_and_move_scores_match_bit_for_bit(stores):
    compact, plain, problems = stores
    rng = np.random.default_rng(5)
    lanes = np.repeat(np.arange(len(problems)), 4)
    paths = np.stack([[rng.integers(problems[b].sizes[i])
                       for i in range(problems[b].n_layers)]
                      for b in lanes]).astype(np.int64)
    nb = get_backend("numpy")
    got = nb.path_costs_stacked(compact.view(), lanes, paths)
    ref = nb.path_costs_stacked(plain.view(), lanes, paths)
    for key in ref:
        np.testing.assert_array_equal(got[key], ref[key], err_msg=key)
    for b, problem in enumerate(problems):   # and the problem's own
        pa = paths[lanes == b]                # elements, summed alike
        own = [np.stack([problem.trans_elems(i, pa[:, i], pa[:, i + 1])[c]
                         for i in range(problem.n_layers - 1)], axis=1)
               for c in range(3)]
        for key, el in zip(("t_trans", "e_trans", "n_switch"), own):
            np.testing.assert_array_equal(got[key][lanes == b],
                                          el.sum(axis=1), err_msg=key)
    t_inf = got["t_op"] + got["t_trans"]
    t_max = float(t_inf.max()) * 1.1
    idle = problems[0].idle
    e_idle = idle.energy_batch(t_max - t_inf)
    for a, b in zip(move_scores(compact.view(), lanes, paths, t_inf,
                                e_idle, t_max, idle),
                    move_scores(plain.view(), lanes, paths, t_inf, e_idle,
                                t_max, idle)):
        np.testing.assert_array_equal(a, b)


def test_member_stacks_widen_to_the_widest_blocks():
    """Stacking lanes with different block counts and sizes pads the
    blocks; every kernel result stays that of the lane alone."""
    small = build_padded(subset_problems(2, n_layers=6, prune=True)[3])
    big = build_padded(subset_problems(9, n_layers=6)[0])
    small = repad(small, big.s_pad)
    stack = stack_padded([small, big])
    assert stack.t_blk.shape[1:] == (max(small.n_blocks, big.n_blocks),
                                     big.block_size, big.block_size)
    nb = get_backend("numpy")
    w = np.array([[0.5, 1.0]])
    for b, alone in enumerate((small, big)):
        np.testing.assert_array_equal(
            nb.dp_multi_stacked(stack, np.repeat(w, 2, 0),
                                np.repeat(w, 2, 0))[b],
            nb.dp_multi(alone, w[0], w[0]))


def test_lane_counters(device_path):
    """Uploads count each lane's distinct blocks; the mirror gauge holds
    the device bytes of the live mirrors and drops with the store; each
    device dispatch names its shape."""
    import gc

    compact, _, problems = _stores(prune=False)
    base = dict(device_path.io_stats)
    w = np.ones((2, 3))
    pend = device_path.dp_multi_lanes(compact, [0, 1], w, w, defer=True)
    pend.get()
    m = device_path._mirror(compact)
    assert device_path.io_stats["lane_blocks"] - base["lane_blocks"] == \
        sum(build_padded(p).n_blocks for p in problems)
    grown = device_path.io_stats["lane_mirror_bytes"] - \
        base["lane_mirror_bytes"]
    assert grown == sum(a.nbytes for a in m.arrays) > 0
    L = problems[0].n_layers
    assert pend.dispatch == ("dp", 0, L, 256, compact.n_blocks, 256, 4, 4)
    del compact, m
    gc.collect()
    assert device_path.io_stats["lane_mirror_bytes"] == \
        base["lane_mirror_bytes"]


def test_sweep_counts_its_lane_dispatches(monkeypatch):
    """``solver_stats["lane_dispatches"]`` is the request's own count:
    its rows add up to the DP and k-best dispatches the sweep made."""
    from conftest import max_rate
    from repro.core import OrchestratorConfig
    from repro.models.edge_cnn import edge_network
    from repro.service import CompileService

    jb = get_backend("jax")
    monkeypatch.setattr(jb, "_cpu", False)
    with CompileService() as svc:
        before = jb.io_stats["kernel_dispatches"]
        sched = svc.compile(
            edge_network("squeezenet1.1", 64),
            max_rate("squeezenet1.1") * 0.5,
            cfg=OrchestratorConfig(policy="pfdnn", n_max_rails=2,
                                   backend="jax"),
            network="squeezenet1.1")
        made = jb.io_stats["kernel_dispatches"] - before
    rows = sched.solver_stats["lane_dispatches"]
    assert rows and sum(r["n"] for r in rows) == made
    assert {r["kind"] for r in rows} <= {"dp", "kbest"}
    assert all(r["rung"] in (1, 4, 16) and r["NB"] >= 1 for r in rows)
    assert JaxBackend._N_DP == 8
