"""Pallas DP-kernel parity and device-resident lane tests.

The jax backend's Pallas mode (``PFDNN_PALLAS`` /
``OrchestratorConfig.pallas``) replaces the ``vmap(lax.scan)`` inner
reductions of the stacked solver calls with fused argmin-gather Pallas
kernels (``repro.kernels.dp_sweep``), and the lanes API keeps every
admitted rail subset's padded tensors resident on device.  Everything
here pins the mode to the numpy backend bit-for-bit:

  - every pipeline golden compiles identically under
    ``pallas="interpret"`` (the CPU-correctness vehicle of the TPU
    kernels);
  - the kernels match both the numpy backend and the jitted lax.scan
    path at the call level, including first-occurrence argmin
    tie-breaking and padded tail lanes;
  - a hypothesis property sweeps random level sets / μ grids;
  - warm sweep rounds move ZERO operand bytes host→device (the
    transfer counters only tick when a lane is first admitted);
  - a lane group pads to its power-of-four rung, and the padded tail
    lanes are dropped.
"""

import json
import pathlib

import numpy as np
import pytest

from conftest import max_rate, random_problem
from repro.core import (
    OrchestratorConfig,
    StackedLambdaTask,
    compile_power_schedule,
    get_backend,
    select_rails_stacked,
)
from repro.core.backend import (
    BucketStack,
    PallasDeviceUnsupported,
    PendingResult,
    StackCaches,
    build_padded,
    lane_rung,
    repad,
    stack_padded,
)
from repro.core.lambda_dp import kbest_rows_to_lists
from repro.models.edge_cnn import edge_network

GOLDEN = json.loads(
    (pathlib.Path(__file__).parent / "golden" / "pipeline.json")
    .read_text())

PALLAS = "jax-pallas-interpret"

_RATES: dict[tuple[str, str], float] = {}


def _rate(network: str, frac: str) -> float:
    key = (network, frac)
    if key not in _RATES:
        _RATES[key] = max_rate(network) * float(frac)
    return _RATES[key]


# ------------------------------------------------ golden bit-identity

@pytest.mark.parametrize("key", sorted(GOLDEN))
def test_golden_compiles_bit_identical_under_pallas(key):
    """Every policy × config of the pipeline goldens, compiled with the
    Pallas interpret backend, reproduces the frozen numpy outputs —
    rails and voltage paths exactly, scalars to float tolerance."""
    network, frac, n_rails, policy = key.split("|")
    golden = GOLDEN[key]
    s = compile_power_schedule(
        edge_network(network), _rate(network, frac),
        cfg=OrchestratorConfig(policy=policy, n_max_rails=int(n_rails),
                               backend="jax", pallas="interpret"),
        network=network)
    if not golden["feasible"]:
        assert s is None
        return
    assert s is not None
    assert s.e_total == pytest.approx(golden["e_total"], rel=1e-9)
    assert s.t_infer == pytest.approx(golden["t_infer"], rel=1e-9)
    assert list(s.rails) == golden["rails"]
    assert [list(v) for v in s.layer_voltages] == golden["layer_voltages"]


# ------------------------------------------- kernel-level parity

def _stack_from(problems):
    padded = [build_padded(p) for p in problems]
    sp = max(p.s_pad for p in padded)
    return stack_padded([repad(p, sp) for p in padded])


def test_pallas_stacked_matches_scan_and_numpy(monkeypatch, rng):
    """The three Pallas kernels against BOTH references on one stack:
    the numpy backend and the jitted lax.scan path (thresholds forced
    to zero so the CPU heuristics cannot route either to the host)."""
    pk = get_backend(PALLAS)
    # "jax" routes to the pallas instance while $PFDNN_PALLAS is set —
    # clear it so jx really is the plain lax.scan backend
    monkeypatch.delenv("PFDNN_PALLAS", raising=False)
    jx = get_backend("jax")
    ref = get_backend("numpy")
    assert pk is not jx and pk.pallas_mode == "interpret"
    monkeypatch.setattr(type(jx), "_JIT_MIN_WORK", 0)
    monkeypatch.setattr(type(jx), "_KBEST_JIT_MIN_WORK", 0)
    problems = [random_problem(rng, n_layers=5, n_states=n)
                for n in (4, 6, 3)]
    stack = _stack_from(problems)
    w_e = rng.random((3, 5))
    w_t = rng.random((3, 5))
    mus = rng.random((3, 3)) * 10.0
    for other in (ref, jx):
        np.testing.assert_array_equal(
            pk.dp_multi_stacked(stack, w_e, w_t),
            other.dp_multi_stacked(stack, w_e, w_t))
        pp, pc = pk.kbest_multi_stacked(stack, mus, 4)
        op, oc = other.kbest_multi_stacked(stack, mus, 4)
        np.testing.assert_array_equal(pc, oc)
        for b in range(3):
            assert kbest_rows_to_lists(pp[b], pc[b]) == \
                kbest_rows_to_lists(op[b], oc[b])
        lanes = np.array([0, 1, 2, 2, 0], dtype=np.int64)
        paths = np.stack([np.asarray(
            pk.dp_multi_stacked(stack, w_e, w_t)[b, 0])
            for b in lanes])
        got = pk.path_costs_stacked(stack, lanes, paths)
        exp = other.path_costs_stacked(stack, lanes, paths)
        for k in exp:
            np.testing.assert_array_equal(got[k], exp[k], err_msg=k)


def test_pallas_single_layer_stack_matches_numpy(rng):
    """L == 1 takes the pure-jnp special case of the jitted wrappers
    (no transition axis for a kernel to reduce) — still bit-exact."""
    pk = get_backend(PALLAS)
    ref = get_backend("numpy")
    problems = [random_problem(rng, n_layers=1, n_states=4)
                for _ in range(2)]
    stack = _stack_from(problems)
    w = rng.random((2, 3))
    np.testing.assert_array_equal(
        pk.dp_multi_stacked(stack, w, w[:, ::-1]),
        ref.dp_multi_stacked(stack, w, w[:, ::-1]))
    pp, pc = pk.kbest_multi_stacked(stack, w[:, :2], 3)
    op, oc = ref.kbest_multi_stacked(stack, w[:, :2], 3)
    np.testing.assert_array_equal(pc, oc)
    np.testing.assert_array_equal(pp[pc > 0], op[oc > 0])


def test_pallas_ties_break_first_occurrence(rng):
    """Duplicate states tie path costs bitwise; the kernels must pick
    the same (first-occurrence) argmin / stable-sort order as numpy —
    paths compared EXACTLY, not just their costs."""
    problems = []
    for _ in range(3):
        p = random_problem(rng, n_layers=4, n_states=5)
        for states in p.layer_states:
            states[1] = states[0]       # exact duplicate per layer
            states[4] = states[3]
        problems.append(p)
    stack = _stack_from(problems)
    pk = get_backend(PALLAS)
    ref = get_backend("numpy")
    w_e = rng.random((3, 4))
    w_t = rng.random((3, 4))
    np.testing.assert_array_equal(
        pk.dp_multi_stacked(stack, w_e, w_t),
        ref.dp_multi_stacked(stack, w_e, w_t))
    pp, pc = pk.kbest_multi_stacked(stack, w_e[:, :2], 6)
    op, oc = ref.kbest_multi_stacked(stack, w_e[:, :2], 6)
    np.testing.assert_array_equal(pc, oc)
    for b in range(3):
        assert kbest_rows_to_lists(pp[b], pc[b]) == \
            kbest_rows_to_lists(op[b], oc[b])


@pytest.mark.parametrize("n_lanes", [3, 5])     # rungs 4 and 16
def test_pallas_padded_tail_lanes_are_dropped(rng, n_lanes):
    """Lane counts off their power-of-four rung are padded with repeats
    of lane 0; the result rows of the real lanes must be untouched by
    the padding."""
    pk = get_backend(PALLAS)
    ref = get_backend("numpy")
    problems = [random_problem(rng, n_layers=3, n_states=4)
                for _ in range(n_lanes)]
    stack = _stack_from(problems)
    assert pk._pad_lanes(stack)[0].n_lanes == lane_rung(n_lanes)
    w = rng.random((n_lanes, 2))
    np.testing.assert_array_equal(
        pk.dp_multi_stacked(stack, w, w + 1.0),
        ref.dp_multi_stacked(stack, w, w + 1.0))


def test_property_pallas_matches_numpy_random_level_sets():
    """Hypothesis property: random level sets and μ grids at one fixed
    padded shape (so the suite compiles each kernel once) — DP paths
    and the k-best frontier match the numpy backend exactly."""
    pytest.importorskip(
        "hypothesis",
        reason="hypothesis not installed (see requirements-dev.txt)")
    from hypothesis import given, settings, strategies as hst

    pk = get_backend(PALLAS)
    ref = get_backend("numpy")

    @settings(max_examples=12, deadline=None)
    @given(seed=hst.integers(0, 2**32 - 1),
           k=hst.integers(min_value=1, max_value=4))
    def prop(seed, k):
        r = np.random.default_rng(seed)
        problems = [random_problem(r, n_layers=3, n_states=4)
                    for _ in range(2)]
        stack = _stack_from(problems)
        w_e = r.random((2, 4))
        w_t = r.random((2, 4))
        mus = np.concatenate(
            [[0.0], np.sort(r.random(3)) * 50.0])[None, :].repeat(
                2, axis=0)
        np.testing.assert_array_equal(
            pk.dp_multi_stacked(stack, w_e, w_t),
            ref.dp_multi_stacked(stack, w_e, w_t))
        pp, pc = pk.kbest_multi_stacked(stack, mus, k)
        op, oc = ref.kbest_multi_stacked(stack, mus, k)
        np.testing.assert_array_equal(pc, oc)
        for b in range(2):
            assert kbest_rows_to_lists(pp[b], pc[b]) == \
                kbest_rows_to_lists(op[b], oc[b])

    prop()


# ------------------------------------- device-resident lane stores

def _lane_store(rng, n=3, n_layers=4, n_states=5):
    pads = [build_padded(random_problem(rng, n_layers=n_layers,
                                        n_states=n_states))
            for _ in range(n)]
    sp = max(p.s_pad for p in pads)
    pads = [repad(p, sp) for p in pads]
    store = BucketStack(pads[0].n_layers, sp)
    lanes = [store.add(("lane", i), p) for i, p in enumerate(pads)]
    return store, lanes


def test_lanes_api_matches_member_stack_and_counts_uploads(rng):
    """The lanes entry points equal the member-stack entry points lane
    for lane, each lane's tensors go host→device exactly ONCE, and
    warm repeats upload nothing."""
    pk = get_backend(PALLAS)
    ref = get_backend("numpy")
    store, lanes = _lane_store(rng)
    base = dict(pk.io_stats)
    w_e = rng.random((3, 4))
    w_t = rng.random((3, 4))
    mus = rng.random((3, 2))
    got = pk.dp_multi_lanes(store, lanes, w_e, w_t)
    exp = ref.dp_multi_stacked(pk._host_member_stack(store, lanes),
                               w_e, w_t)
    np.testing.assert_array_equal(got, exp)
    gp, gc = pk.kbest_multi_lanes(store, lanes, mus, 4)
    ep, ec = ref.kbest_multi_stacked(pk._host_member_stack(store, lanes),
                                     mus, 4)
    np.testing.assert_array_equal(gc, ec)
    for b in range(3):
        assert kbest_rows_to_lists(gp[b], gc[b]) == \
            kbest_rows_to_lists(ep[b], ec[b])
    pl = np.asarray([0, 2, 1, 1], dtype=np.int64)
    pp_ = rng.integers(0, 5, (4, 4)).astype(np.int64)
    gotc = pk.path_costs_lanes(store, pl, pp_)
    expc = ref.path_costs_stacked(store.view(), pl, pp_)
    for k in expc:
        np.testing.assert_array_equal(gotc[k], expc[k], err_msg=k)
    cold = pk.io_stats["h2d_lane_uploads"] - base["h2d_lane_uploads"]
    assert cold == len(lanes)
    assert pk.io_stats["h2d_lane_bytes"] > base["h2d_lane_bytes"]
    # warm repeats: zero further operand uploads, dispatches still tick
    mark = dict(pk.io_stats)
    pk.dp_multi_lanes(store, lanes, w_e, w_t)
    pk.kbest_multi_lanes(store, lanes, mus, 4)
    pk.path_costs_lanes(store, pl, pp_)
    assert pk.io_stats["h2d_lane_uploads"] == mark["h2d_lane_uploads"]
    assert pk.io_stats["h2d_lane_bytes"] == mark["h2d_lane_bytes"]
    assert pk.io_stats["kernel_dispatches"] >= \
        mark["kernel_dispatches"] + 3


def test_scan_lanes_price_paths_on_the_host(monkeypatch, rng):
    """With the CPU routing off (as on a TPU) the scan backend's DP
    runs on the device mirror, which holds only the DP operands, while
    path costs come from the store's host tensors — no dispatch, and
    numpy's ledger to the last bit."""
    jb = get_backend("jax")
    monkeypatch.setattr(jb, "_cpu", False)
    store, lanes = _lane_store(rng)
    base = dict(jb.io_stats)
    w = rng.random((len(lanes), 4))
    np.testing.assert_array_equal(
        jb.dp_multi_lanes(store, lanes, w, w),
        get_backend("numpy").dp_multi_stacked(
            jb._host_member_stack(store, lanes), w, w))
    assert jb.io_stats["h2d_lane_bytes"] - base["h2d_lane_bytes"] == sum(
        getattr(store, nm)[:len(lanes)].nbytes
        for nm in ("_t_op", "_e_op", "_valid", "_t_blk", "_e_blk",
                   "_block_of", "_rsel", "_csel"))
    mark = dict(jb.io_stats)
    pl = np.asarray([0, 2, 1, 1], dtype=np.int64)
    pp_ = rng.integers(0, 5, (4, 4)).astype(np.int64)
    got = jb.path_costs_lanes(store, pl, pp_)
    assert jb.io_stats == mark
    exp = get_backend("numpy").path_costs_stacked(store.view(), pl, pp_)
    for k in exp:
        np.testing.assert_array_equal(got[k], exp[k], err_msg=k)


def test_lane_admission_uploads_only_the_new_lane(rng):
    """Growing a warm store re-uses the resident mirror: admitting one
    more lane uploads exactly that lane."""
    pk = get_backend(PALLAS)
    store, lanes = _lane_store(rng)
    w = np.ones((len(lanes), 2))
    pk.dp_multi_lanes(store, lanes, w, w)
    mark = pk.io_stats["h2d_lane_uploads"]
    extra = repad(build_padded(random_problem(
        rng, n_layers=store._t_op.shape[1],
        n_states=4)), store._t_op.shape[2])
    lanes.append(store.add(("lane", "extra"), extra))
    w = np.ones((len(lanes), 2))
    pk.dp_multi_lanes(store, lanes, w, w)
    assert pk.io_stats["h2d_lane_uploads"] == mark + 1


def test_warm_sweep_rounds_upload_nothing(monkeypatch, rng):
    """End-to-end transfer counting through the round scheduler: a
    second full sweep on the same persistent lane stores (the service
    steady state) runs entirely from the device mirrors."""
    from test_stacked_sweep import _MasterInstance
    from repro.core.rails import all_rail_subsets

    bk = get_backend(PALLAS)
    inst = _MasterInstance(1, n_layers=4, n_levels=4,
                           thresh_frac=0.3, tie_energies=False)

    def make_task(idx, subset, hint=None):
        # a content-derived lane key is what lets the persistent
        # stores recognize the subset across sweeps (the fleet
        # service derives one from the problem content)
        return StackedLambdaTask(idx, subset, inst.problem(subset),
                                 lane_key=("subset", subset),
                                 caches=caches)

    caches = StackCaches()
    ref = select_rails_stacked(
        all_rail_subsets(inst.levels, 3), make_task, max_live=8)
    cold = select_rails_stacked(
        all_rail_subsets(inst.levels, 3), make_task, max_live=8,
        backend=PALLAS, caches=caches)
    mark = dict(bk.io_stats)
    warm = select_rails_stacked(
        all_rail_subsets(inst.levels, 3), make_task, max_live=8,
        backend=PALLAS, caches=caches)
    assert bk.io_stats["h2d_lane_uploads"] == mark["h2d_lane_uploads"]
    assert bk.io_stats["h2d_lane_bytes"] == mark["h2d_lane_bytes"]
    # and all three sweeps selected identically
    for got in (cold, warm):
        assert got[1] == ref[1]
        if ref[0] is not None:
            assert got[0]["e_total"] == ref[0]["e_total"]
            assert got[0]["path"] == ref[0]["path"]


@pytest.mark.parametrize("n,rung", [(1, 1), (2, 4), (4, 4), (5, 16),
                                    (16, 16)])
def test_lane_rung(n, rung):
    assert lane_rung(n) == rung


def test_pending_result_defers_and_memoizes():
    calls = []

    def fn():
        calls.append(1)
        return 42

    pend = PendingResult(fn)
    assert not calls                       # nothing ran at dispatch
    assert pend.get() == 42
    assert pend.get() == 42
    assert len(calls) == 1                 # collected exactly once
    assert PendingResult.ready("x").get() == "x"


# ---------------------------------------- configuration / routing

def test_orchestrator_config_pallas_validation():
    cfg = OrchestratorConfig(backend="jax", pallas="interpret")
    assert cfg.backend == "jax-pallas-interpret"
    with pytest.raises(PallasDeviceUnsupported, match="ROADMAP"):
        OrchestratorConfig(pallas="device")
    with pytest.raises(ValueError, match="pallas"):
        OrchestratorConfig(pallas="nope")
    with pytest.raises(ValueError, match="numpy"):
        OrchestratorConfig(backend="numpy", pallas="interpret")


def test_pallas_env_var_routes_the_jax_backend(monkeypatch):
    monkeypatch.setenv("PFDNN_PALLAS", "interpret")
    assert get_backend("jax") is get_backend(PALLAS)
    monkeypatch.setenv("PFDNN_PALLAS", "off")
    assert get_backend("jax") is not get_backend(PALLAS)
    monkeypatch.setenv("PFDNN_PALLAS", "bogus")
    with pytest.raises(ValueError, match="PFDNN_PALLAS"):
        get_backend("jax")


def test_pallas_backend_is_cached_and_named(monkeypatch):
    pk = get_backend(PALLAS)
    assert pk is get_backend(PALLAS)
    assert pk.name == "jax"                # stats/golden compatibility
    assert pk.pallas_mode == "interpret"
    monkeypatch.delenv("PFDNN_PALLAS", raising=False)
    assert get_backend("jax").pallas_mode is None
