"""Subset-stacked sweep engine + backend k-best frontier: equivalence
with the scalar / sequential implementations.

The contracts under test (see ISSUE 3 / ROADMAP):
  - the backend ``kbest_multi`` frontier (numpy + jitted jax) matches
    the scalar pure-numpy ``kbest_paths`` kernel per μ, exactly;
  - every stacked kernel is per-lane bit-identical to the non-stacked
    kernel on that lane's own (re-padded) tensors;
  - ``select_rails_stacked`` selects the identical
    ``(best_subset, e_total, path)`` as the sequential ``select_rails``
    across random level sets, deadlines, bucket mixes, live caps, and
    worker counts — ties and infeasible subsets included;
  - the golden pipeline passes under ``stack_subsets=True`` on both
    backends, and the legacy per-subset path stays intact behind
    ``stack_subsets=False``.
"""

import json
import pathlib

import numpy as np
import pytest

from conftest import max_rate, random_problem
from repro.core import (
    OrchestratorConfig,
    StackedLambdaTask,
    available_backends,
    compile_power_schedule,
    kbest_paths,
    kbest_paths_multi,
    get_backend,
    select_rails,
    select_rails_stacked,
    solve_lambda_dp,
)
from repro.core.lambda_dp import kbest_rows_to_lists
from repro.core.backend import (
    BucketStack,
    JaxBackend,
    build_padded,
    repad,
    stack_padded,
)
from repro.core.problem import IdleModel, ScheduleProblem, StateCost
from repro.core.rails import all_rail_subsets
from repro.hw.dvfs import TransitionModel
from repro.models.edge_cnn import edge_network

GOLDEN = json.loads(
    (pathlib.Path(__file__).parent / "golden" / "pipeline.json")
    .read_text())

BACKENDS = list(available_backends())


# --------------------------------------- backend k-best frontier parity

@pytest.mark.parametrize("shape", [(3, 40, 5), (3, 40)])
def test_jax_smallest_k_is_numpy_stable_order(shape):
    """The jax k-best's selection (k rounds of min + first hit, not a
    sort) returns numpy's stable argsort prefix exactly — heavy ties,
    inf ties and all-inf columns included."""
    pytest.importorskip("jax")
    import jax

    jb = get_backend("jax")
    k = 10
    rng = np.random.default_rng(0)
    for trial in range(12):
        x = rng.integers(0, 6, shape).astype(float)
        x[x == 5] = np.inf
        if trial % 3 == 0:
            x[:, :k + 3] = np.inf          # inf ties ahead of finite ones
        if trial % 4 == 0:
            x[0] = np.inf                  # a row with nothing finite
        with jax.enable_x64(True):
            got = np.asarray(jax.jit(
                lambda a: jb._smallest_k(a, k))(x))
        want = np.argsort(x, axis=1, kind="stable")[:, :k]
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("seed", range(4))
def test_kbest_multi_matches_scalar_kernel(backend, seed):
    """The pluggable-backend fused multi-μ frontier returns exactly the
    scalar pure-numpy ``kbest_paths`` per μ — non-stacked path."""
    rng = np.random.default_rng(seed)
    prob = random_problem(rng, n_layers=5, n_states=4)
    mus = [0.0, -prob.idle.p_sleep, 1e-3, 0.7, 50.0]
    k = 6
    multi = kbest_paths_multi(prob, mus, k, backend=backend)
    for q, mu in enumerate(mus):
        assert multi[q] == kbest_paths(prob, mu, k), (backend, mu)


@pytest.mark.parametrize("backend", BACKENDS)
def test_kbest_stacked_matches_per_lane(backend):
    """Stacked frontier lanes are bit-identical to the non-stacked
    kernel on each lane's own re-padded tensors (mixed buckets)."""
    bk = get_backend(backend)
    rng = np.random.default_rng(7)
    problems = [random_problem(rng, n_layers=5, n_states=n)
                for n in (3, 5, 4, 7)]         # buckets 4 and 8 mixed
    padded = [build_padded(p) for p in problems]
    sp = max(p.s_pad for p in padded)
    stack = stack_padded([repad(p, sp) for p in padded])
    mus = np.array([[0.0, 3.5], [1e-3, 50.0], [0.7, 0.7], [-1e-5, 2.0]])
    k = 5
    paths, counts = bk.kbest_multi_stacked(stack, mus, k)
    for b, p in enumerate(padded):
        ref_p, ref_c = bk.kbest_multi(repad(p, sp), mus[b], k)
        np.testing.assert_array_equal(counts[b], ref_c)
        assert kbest_rows_to_lists(paths[b], counts[b]) == \
            kbest_rows_to_lists(ref_p, ref_c)


@pytest.mark.parametrize("backend", BACKENDS)
def test_dp_stacked_matches_per_lane(backend):
    bk = get_backend(backend)
    rng = np.random.default_rng(11)
    problems = [random_problem(rng, n_layers=6, n_states=n)
                for n in (4, 6, 3)]
    padded = [build_padded(p) for p in problems]
    sp = max(p.s_pad for p in padded)
    stack = stack_padded([repad(p, sp) for p in padded])
    w_t = np.array([[0.0, 1e-3, 4.0], [1.0, 0.5, 60.0], [0.0, 0.0, 9.0]])
    w_e = np.ones_like(w_t)
    w_e[0, 0] = 0.0                            # a min-time row
    paths = bk.dp_multi_stacked(stack, w_e, w_t)
    for b, p in enumerate(padded):
        ref = bk.dp_multi(repad(p, sp), w_e[b], w_t[b])
        np.testing.assert_array_equal(paths[b], ref)


@pytest.mark.parametrize("backend", BACKENDS)
def test_path_costs_stacked_matches_per_lane(backend):
    bk = get_backend(backend)
    rng = np.random.default_rng(13)
    problems = [random_problem(rng, n_layers=5, n_states=4)
                for _ in range(3)]
    padded = [build_padded(p) for p in problems]
    stack = stack_padded(padded)
    paths = np.array([[int(rng.integers(4)) for _ in range(5)]
                      for _ in range(9)])
    lanes = np.array([0, 1, 2, 2, 1, 0, 1, 0, 2])
    got = bk.path_costs_stacked(stack, lanes, paths)
    for r in range(len(paths)):
        ref = bk.path_costs(problems[lanes[r]], paths[r:r + 1])
        for key in ("t_op", "e_op", "t_trans", "e_trans", "n_switch"):
            assert got[key][r] == ref[key][0], (backend, key, r)


@pytest.mark.skipif("jax" not in BACKENDS, reason="jax not installed")
def test_jax_jitted_kernels_match_numpy(monkeypatch):
    """Force the jitted scan kernels (the CPU heuristics would route
    these small slabs to the host numpy kernels) and pin exact parity
    for the DP and the k-best frontier, stacked and non-stacked."""
    bk = get_backend("jax")
    monkeypatch.setattr(type(bk), "_JIT_MIN_WORK", 0)
    monkeypatch.setattr(type(bk), "_KBEST_JIT_MIN_WORK", 0)
    ref = get_backend("numpy")
    rng = np.random.default_rng(3)
    problems = [random_problem(rng, n_layers=5, n_states=n)
                for n in (4, 6)]
    padded = [build_padded(p) for p in problems]
    sp = max(p.s_pad for p in padded)
    stack = stack_padded([repad(p, sp) for p in padded])
    mus = np.array([[0.0, 4.0], [1e-3, 30.0]])
    for b, p in enumerate(padded):
        np.testing.assert_array_equal(
            bk.dp_multi(p, np.ones(2), mus[b]),
            ref.dp_multi(p, np.ones(2), mus[b]))
        jp, jc = bk.kbest_multi(p, mus[b], 4)
        rp, rc = ref.kbest_multi(p, mus[b], 4)
        np.testing.assert_array_equal(jc, rc)
        assert kbest_rows_to_lists(jp, jc) == kbest_rows_to_lists(rp, rc)
    np.testing.assert_array_equal(
        bk.dp_multi_stacked(stack, np.ones((2, 2)), mus),
        ref.dp_multi_stacked(stack, np.ones((2, 2)), mus))
    jp, jc = bk.kbest_multi_stacked(stack, mus, 4)
    rp, rc = ref.kbest_multi_stacked(stack, mus, 4)
    np.testing.assert_array_equal(jc, rc)
    for b in range(2):
        assert kbest_rows_to_lists(jp[b], jc[b]) == \
            kbest_rows_to_lists(rp[b], rc[b])


def _lane_store(rng, n_lanes: int, n_layers: int, n_states: int
                ) -> BucketStack:
    store = BucketStack(n_layers, n_states)
    for i in range(n_lanes):
        store.add(("lane", i), build_padded(random_problem(
            rng, n_layers=n_layers, n_states=n_states)))
    return store


@pytest.mark.skipif("jax" not in BACKENDS, reason="jax not installed")
@pytest.mark.parametrize("n_lanes", [1, 3, 5])      # rungs 1, 4 and 16
def test_lanes_at_every_rung_match_numpy(n_lanes):
    """The lanes API pads a group to its rung and drops the pad lanes:
    the DP's paths and the k-best frontier are numpy's, bit for bit.
    The slabs clear the CPU routing floors, so even one lane runs the
    jitted programs."""
    bk = get_backend("jax")
    ref = get_backend("numpy")
    rng = np.random.default_rng(n_lanes)
    L, S, K, k = 8, 64, 16, 8
    assert K * L * S * S >= bk._JIT_MIN_WORK
    assert K * k * L * S * S >= bk._KBEST_JIT_MIN_WORK
    store = _lane_store(rng, n_lanes, L, S)
    lanes = list(range(n_lanes))
    members = bk._host_member_stack(store, lanes)
    w_e = np.ones((n_lanes, K))
    w_t = rng.uniform(0.0, 0.5, (n_lanes, K))
    mus = rng.uniform(0.0, 0.5, (n_lanes, K))
    before = bk.io_stats["kernel_dispatches"]
    np.testing.assert_array_equal(
        bk.dp_multi_lanes(store, lanes, w_e, w_t),
        ref.dp_multi_stacked(members, w_e, w_t))
    got_p, got_c = bk.kbest_multi_lanes(store, lanes, mus, k)
    exp_p, exp_c = ref.kbest_multi_stacked(members, mus, k)
    np.testing.assert_array_equal(got_c, exp_c)
    np.testing.assert_array_equal(got_p, exp_p)
    assert bk.io_stats["kernel_dispatches"] == before + 2


@pytest.mark.skipif("jax" not in BACKENDS, reason="jax not installed")
def test_lane_programs_are_built_up_to_the_top_rung():
    """A store's lane programs are built at every rung up to the highest
    any of its dispatches needed: once it has reached rung 16, groups of
    1, 2 and 5 lanes compile nothing and compute rung·Kp slots each."""
    bk = JaxBackend()
    bk._cpu = False                # the jitted programs at any size
    rng = np.random.default_rng(7)
    store = _lane_store(rng, 16, 4, 4)
    dp, kbest = bk._lanes_fn("dp"), bk._lanes_fn("kbest", 3)

    def dispatch(n: int) -> dict:
        before = dict(bk.io_stats)
        lanes = list(range(n))
        bk.dp_multi_lanes(store, lanes, np.ones((n, 3)),
                          rng.random((n, 3)))
        bk.kbest_multi_lanes(store, lanes, rng.random((n, 3)), 3)
        return {key: bk.io_stats[key] - before[key] for key in before}

    # both programs first dispatch at rung 1, with nothing below it
    assert dispatch(1)["lane_rung_builds"] == 0
    # the DP raises the top rung to 16: the DP is built at rung 4, the
    # k-best (already built at rung 1) at rungs 4 and 16
    assert dispatch(16)["lane_rung_builds"] == 3
    assert dp._cache_size() == kbest._cache_size() == 3
    for n, rung in ((1, 1), (2, 4), (5, 16)):
        delta = dispatch(n)
        assert delta["lane_rung_builds"] == 0
        assert dp._cache_size() == kbest._cache_size() == 3
        assert delta["kernel_dispatches"] == 2
        assert delta["lane_slots"] == 2 * rung * 4          # Kp = 4
        assert delta["lane_slots_used"] == 2 * n * 3


@pytest.mark.skipif("jax" not in BACKENDS, reason="jax not installed")
def test_concurrent_lane_dispatches_build_each_rung_once():
    """Threads sharing one store (as compilations of one service do)
    dispatch groups of every width: each program ends up built at rungs
    1, 4 and 16 only, and every result is numpy's."""
    import sys
    import threading

    bk = JaxBackend()
    bk._cpu = False
    ref = get_backend("numpy")
    store = _lane_store(np.random.default_rng(11), 16, 4, 4)
    errors: list = []

    def work(seed: int) -> None:
        rng = np.random.default_rng(seed)
        try:
            for _ in range(6):
                lanes = list(rng.choice(16, int(rng.integers(1, 17)),
                                        replace=False))
                w = rng.random((len(lanes), 3))
                np.testing.assert_array_equal(
                    bk.dp_multi_lanes(store, lanes, np.ones_like(w), w),
                    ref.dp_multi_stacked(
                        bk._host_member_stack(store, lanes),
                        np.ones_like(w), w))
        except Exception as exc:     # reported by the main thread
            errors.append(exc)

    saved = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(saved)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    assert store.top_rung == 16
    assert bk._lanes_fn("dp")._cache_size() == 3


# ------------------------------- stacked sweep vs sequential selection

class _MasterInstance:
    """A random sweep instance with sound cuts: per-layer latency is
    monotone non-increasing in voltage (so the infeasibility ceiling is
    exact, as on the real accelerator) and Σ min E_op is a true lower
    bound (so the incumbent cut is sound)."""

    def __init__(self, seed: int, n_layers: int, n_levels: int,
                 thresh_frac: float, tie_energies: bool):
        rng = np.random.default_rng(seed)
        self.levels = tuple(sorted(
            round(float(v), 3)
            for v in rng.uniform(0.7, 1.3, size=n_levels)))
        self.base_t = rng.uniform(1e-4, 1e-3, size=n_layers)
        if tie_energies:
            # energy independent of voltage → whole size classes of
            # subsets tie on e_total; enumeration order must break them
            self.base_e = np.repeat(
                rng.uniform(1e-6, 1e-4, size=(n_layers, 1)),
                n_levels, axis=1)
        else:
            self.base_e = rng.uniform(1e-6, 1e-4,
                                      size=(n_layers, n_levels))
        # deadline set so subsets whose max rail is below a threshold
        # level are provably infeasible (exercises the vmax ceiling)
        lo, hi = min(self.levels), max(self.levels)
        v_thresh = lo + thresh_frac * (hi - lo)
        self.t_max = float(self.base_t.sum() / v_thresh)
        self.idle = IdleModel(p_idle=1e-3, p_sleep=1e-5,
                              e_sleep_wake=1e-8, t_sleep_wake=1e-6)
        self.tm = TransitionModel(v_min=lo, v_max=hi)

    def problem(self, rails: tuple[float, ...]) -> ScheduleProblem:
        cols = [self.levels.index(v) for v in sorted(rails)]
        layers = [[StateCost(voltages=(self.levels[c],),
                             t_op=float(self.base_t[i] / self.levels[c]),
                             e_op=float(self.base_e[i][c]))
                   for c in cols]
                  for i in range(len(self.base_t))]
        return ScheduleProblem(layer_states=layers, t_max=self.t_max,
                               idle=self.idle, transition_model=self.tm,
                               rails=tuple(sorted(rails)))

    def bound(self, rails: tuple[float, ...]) -> float:
        cols = [self.levels.index(v) for v in sorted(rails)]
        return float(self.base_e[:, cols].min(axis=1).sum())


def _sweep_both_ways(inst: _MasterInstance, n_max: int, *,
                     max_live: int, workers: int | None = None):
    def solve_fn(subset):
        best, _, stats = solve_lambda_dp(inst.problem(subset))
        if best is None:
            return None
        best = dict(best)
        best["rails"] = subset
        best["lambda_star"] = stats.lambda_star
        return best

    def make_task(idx, subset, hint=None):
        # hint deliberately ignored: identical probe sequences are what
        # make the stacked-vs-sequential comparison exact
        return StackedLambdaTask(idx, subset, inst.problem(subset))

    seq = select_rails(inst.levels, n_max, solve_fn,
                       bound_fn=inst.bound, workers=workers)
    stk = select_rails_stacked(
        all_rail_subsets(inst.levels, n_max), make_task,
        bound_fn=inst.bound, max_live=max_live)
    return seq, stk


@pytest.mark.parametrize("seed,max_live", [(0, 1), (1, 3), (2, 16),
                                           (3, 5), (4, 16)])
def test_stacked_sweep_matches_sequential(seed, max_live):
    inst = _MasterInstance(seed, n_layers=4, n_levels=4,
                           thresh_frac=0.5, tie_energies=False)
    (b_seq, s_seq, st_seq), (b_stk, s_stk, st_stk) = _sweep_both_ways(
        inst, 3, max_live=max_live)
    assert (b_seq is None) == (b_stk is None)
    assert s_stk == s_seq
    if b_seq is not None:
        assert b_stk["e_total"] == b_seq["e_total"]      # bit-identical
        assert b_stk["path"] == b_seq["path"]
    assert st_stk["subsets_total"] == st_seq["subsets_total"]
    assert (st_stk["subsets_solved"] + st_stk["subsets_skipped"]
            + st_stk["subsets_cut"]) == st_stk["subsets_total"]


def test_stacked_sweep_ties_and_infeasible_band():
    """Size-class e_total ties + an infeasible low-voltage band: the
    stacked scheduler must keep the sequential tie winner (earliest in
    enumeration order) no matter how rounds interleave."""
    for seed in range(3):
        inst = _MasterInstance(seed, n_layers=3, n_levels=5,
                               thresh_frac=0.6, tie_energies=True)
        for max_live in (1, 4, 16):
            (b_seq, s_seq, _), (b_stk, s_stk, _) = _sweep_both_ways(
                inst, 2, max_live=max_live)
            assert s_stk == s_seq, (seed, max_live)
            if b_seq is not None:
                assert b_stk["e_total"] == b_seq["e_total"]


def test_stacked_sweep_all_infeasible():
    inst = _MasterInstance(5, n_layers=3, n_levels=3,
                           thresh_frac=0.5, tie_energies=False)
    inst.t_max = 1e-9                     # nothing can meet the deadline
    (b_seq, s_seq, _), (b_stk, s_stk, st) = _sweep_both_ways(
        inst, 2, max_live=4)
    assert b_seq is None and b_stk is None
    assert s_seq is None and s_stk is None
    assert st["subsets_solved"] + st["subsets_skipped"] \
        + st["subsets_cut"] == st["subsets_total"]


try:
    from hypothesis import given, settings, strategies as hst

    @settings(max_examples=12, deadline=None)
    @given(seed=hst.integers(0, 10_000),
           n_layers=hst.integers(2, 5),
           n_levels=hst.integers(3, 5),
           thresh_frac=hst.floats(0.0, 1.2),
           tie=hst.booleans(),
           max_live=hst.sampled_from([1, 2, 4, 16]),
           workers=hst.sampled_from([None, 3]))
    def test_property_stacked_equals_sequential(seed, n_layers, n_levels,
                                                thresh_frac, tie,
                                                max_live, workers):
        """Random level sets, deadlines, bucket mixes, live caps, and
        worker counts: identical (best_subset, e_total, rails)."""
        inst = _MasterInstance(seed, n_layers, n_levels, thresh_frac, tie)
        (b_seq, s_seq, _), (b_stk, s_stk, _) = _sweep_both_ways(
            inst, 2, max_live=max_live, workers=workers)
        assert s_stk == s_seq
        assert (b_seq is None) == (b_stk is None)
        if b_seq is not None:
            assert b_stk["e_total"] == b_seq["e_total"]
            assert b_stk["rails"] == b_seq["rails"]
except ImportError:                                  # pragma: no cover
    pass


def test_aborted_run_evicts_member_stacks():
    """A fleet that dies mid-round (backend error, interrupt) must not
    strand its uid-keyed member stacks in a possibly store-owned
    StackCaches — no later run can ever hit those keys."""
    from repro.core.backend import NumpyBackend, StackCaches
    from repro.core.rails import StackedSweep, run_stacked_sweeps

    class Boom(Exception):
        pass

    class FailingBackend(NumpyBackend):
        def __init__(self):
            self.calls = 0

        def dp_multi_stacked(self, *args, **kwargs):
            self.calls += 1
            if self.calls >= 2:
                raise Boom()
            return super().dp_multi_stacked(*args, **kwargs)

    inst = _MasterInstance(0, n_layers=4, n_levels=4,
                           thresh_frac=0.5, tie_energies=False)
    caches = StackCaches()
    sweep = StackedSweep(
        all_rail_subsets(inst.levels, 3),
        lambda idx, s, hint=None: StackedLambdaTask(
            idx, s, inst.problem(s)))
    with pytest.raises(Boom):
        run_stacked_sweeps([sweep], backend=FailingBackend(),
                           caches=caches)
    assert caches.member_stacks == {}


# ------------------------------------------ end-to-end + golden pins

def _compile(network, frac, n_rails, policy, **cfg_kwargs):
    return compile_power_schedule(
        edge_network(network), max_rate(network) * frac,
        cfg=OrchestratorConfig(policy=policy, n_max_rails=n_rails,
                               **cfg_kwargs),
        network=network)


def test_batch_lambda_off_routes_to_legacy_sweep():
    """batch_lambda=False means the legacy scalar bisection — the
    stacked engine (which is the batched machine by construction) must
    step aside even when stack_subsets is left at its default."""
    s = _compile("squeezenet1.1", 0.9, 2, "pfdnn", batch_lambda=False)
    assert "stacked_rounds" not in s.solver_stats
    ref = _compile("squeezenet1.1", 0.9, 2, "pfdnn")
    assert s.rails == ref.rails
    assert s.e_total == pytest.approx(ref.e_total, rel=1e-9)


def test_stacked_compile_matches_legacy_sweep():
    stacked = _compile("squeezenet1.1", 0.9, 2, "pfdnn",
                       stack_subsets=True)
    legacy = _compile("squeezenet1.1", 0.9, 2, "pfdnn",
                      stack_subsets=False)
    assert stacked.rails == legacy.rails
    assert stacked.layer_voltages == legacy.layer_voltages
    assert stacked.e_total == pytest.approx(legacy.e_total, rel=1e-9)
    assert "stacked_rounds" in stacked.solver_stats
    assert "stacked_rounds" not in legacy.solver_stats


@pytest.mark.parametrize("backend", BACKENDS)
def test_golden_pipeline_under_stacked_sweep(backend):
    key = "squeezenet1.1|0.9|2|pfdnn"
    golden = GOLDEN[key]
    network, frac, n_rails, policy = key.split("|")
    s = _compile(network, float(frac), int(n_rails), policy,
                 backend=backend, stack_subsets=True)
    assert s.e_total == pytest.approx(golden["e_total"], rel=1e-9)
    assert list(s.rails) == golden["rails"]
    assert [list(v) for v in s.layer_voltages] == golden["layer_voltages"]


def test_golden_pipeline_under_legacy_sweep():
    key = "squeezenet1.1|0.9|2|pfdnn"
    golden = GOLDEN[key]
    network, frac, n_rails, policy = key.split("|")
    s = _compile(network, float(frac), int(n_rails), policy,
                 stack_subsets=False)
    assert s.e_total == pytest.approx(golden["e_total"], rel=1e-9)
    assert list(s.rails) == golden["rails"]
    assert [list(v) for v in s.layer_voltages] == golden["layer_voltages"]
