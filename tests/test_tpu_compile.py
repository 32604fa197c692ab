"""The jax backend's sweep programs compile for a TPU v5e chip.

No chip is needed: the TPU compiler compiles for a *described* v5e
topology (``jax.experimental.topologies``) and refuses there what the
chip would refuse — unsupported dtypes or gathers, misaligned blocks, a
program too large for the device.  The shapes are the chip path's real
ones: the ``(L, S_pad)`` buckets, block counts and block sizes of the
largest edge network's ``BucketStack`` lane stores after a CPU compile
at the paper's setting, and the five-rail stores of S_pad 256 of the
deep networks (the ``edge40nm-5rail-deep`` benchmark deployment), at
the lane / λ-batch buckets and mirror capacity the round scheduler
uses, in float64 (the numeric contract).

The topology is described inside a fixture, never while the module is
imported: only one process at a time may load the TPU library, and
every test worker imports every test file.

Also here: the Pallas *device* mode is refused on every channel that
can request it (the TPU compiler rejects those kernels).
"""

import numpy as np
import pytest

from conftest import max_rate
from repro.core import OrchestratorConfig, get_backend
from repro.core.backend import JaxBackend, PallasDeviceUnsupported
from repro.models.edge_cnn import edge_network

jax = pytest.importorskip("jax")

NETWORK = "mobilevit-xxs"       # the deepest of EDGE_NETWORKS (70 layers)
LANES = 16                      # lane bucket of a full round (max_live)
LAMBDAS = 16                    # λ-batch column bucket
K_BEST = 10                     # OrchestratorConfig.k_candidates
MUS = 4                         # μ columns of a k-best round


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it
    enabled = jax.config.values["jax_enable_compilation_cache"]
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def buckets():
    """``(L, S_pad, NB, SB, cap)`` of every lane store a CPU compile of
    the largest edge network fills at the paper's setting."""
    from repro.models.edge_cnn import edge_network
    from repro.service import CompileService

    with CompileService() as svc:
        sched = svc.compile(
            edge_network(NETWORK), max_rate(NETWORK) * 0.9,
            cfg=OrchestratorConfig(policy="pfdnn", n_max_rails=3,
                                   backend="numpy"),
            network=NETWORK)
        assert sched is not None
        stores = svc.store.stack_caches.buckets.values()
        sigs = sorted({_shape(bs) for bs in stores})
    assert sigs and all(s[0] == len(edge_network(NETWORK)) for s in sigs)
    return sigs


def _shape(bs) -> tuple[int, int, int, int, int]:
    """``(L, S_pad, NB, SB, mirror capacity)`` of a lane store."""
    L, S = bs._t_op.shape[1:]
    return (L, S, bs.n_blocks, bs.block_size,
            max(JaxBackend._MIRROR_MIN_CAP, bs._cap))


@pytest.fixture(scope="module")
def deep_buckets():
    """The S_pad 256 lane stores of the deep networks' five-rail
    subsets (the ``edge40nm-5rail-deep`` deployment), built from the
    pruned subset problems the sweep admits — no solve needed."""
    from repro.core.backend import BucketStack, build_padded
    from repro.core.context import CompilationContext
    from repro.core.pruning import prune_problem
    from repro.core.rails import all_rail_subsets

    shapes = []
    for net in ("mobilevit-xxs", "resnet18"):
        ctx = CompilationContext(edge_network(net), max_rate(net),
                                 network=net)
        stores: dict = {}
        for rails in all_rail_subsets(ctx.levels, 5):
            if len(rails) < 5:
                continue
            pruned, _ = prune_problem(ctx.problem_for(
                rails, gating=True, allow_sleep=True,
                materialize_states=False))
            padded = build_padded(pruned)
            stores.setdefault(padded.s_pad, BucketStack(
                padded.n_layers, padded.s_pad)).add(rails, padded)
        assert 256 in stores, (net, sorted(stores))
        shapes.append(_shape(stores[256]))
    return shapes


def _specs(one_chip, L, S, NB, SB, lead):
    """ShapeDtypeStructs of the DP/k-best operand set with leading
    axis ``lead`` (JaxBackend._DP_NAMES): t_op, e_op, valid, t_blk,
    e_blk, block_of, rsel, csel."""
    f64 = np.dtype("float64")
    i32 = np.dtype("int32")

    def sds(shape, dtype=f64):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return [sds((lead, L, S)), sds((lead, L, S)),
            sds((lead, L, S), np.dtype(bool)),
            sds((lead, NB, SB, SB)), sds((lead, NB, SB, SB)),
            sds((lead, L - 1), i32), sds((lead, L - 1, S), i32),
            sds((lead, L - 1, S), i32)], sds


def _program(jb, name, one_chip, L, S, NB, SB, cap):
    """(jitted program, argument specs) of one chip-path program."""
    i64 = np.dtype("int64")
    if name == "dp_stacked":
        ops, sds = _specs(one_chip, L, S, NB, SB, LANES)
        return jb._dp_stacked, ops + [sds((LANES, LAMBDAS))] * 2
    if name == "kbest_stacked":
        ops, sds = _specs(one_chip, L, S, NB, SB, LANES)
        return jb._kbest_fn(K_BEST, True), ops + [sds((LANES, MUS))]
    ops, sds = _specs(one_chip, L, S, NB, SB, cap)
    idx = sds((LANES,), i64)
    if name == "dp_lanes":
        return jb._lanes_fn("dp"), ops + [idx] + [sds((LANES, LAMBDAS))] * 2
    if name == "kbest_lanes":
        return jb._lanes_fn("kbest", K_BEST), ops + [idx,
                                                      sds((LANES, MUS))]
    # lane_upload: a round's newly admitted lanes written into the
    # mirror's largest tensor as one block
    blk = ops[3]
    return jb._set_block, [blk, sds((LANES, NB, SB, SB)), sds((), i64)]


def _compiles(jb, name, one_chip, shapes):
    with jax.enable_x64(True):
        for shape in shapes:
            fn, args = _program(jb, name, one_chip, *shape)
            compiled = fn.lower(*args).compile()
            mem = compiled.memory_analysis()
            # one program of the sweep stays far inside a 16 GB chip
            assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
                < 1 << 32, (name, shape)


@pytest.mark.parametrize("name", ["dp_stacked", "kbest_stacked",
                                  "dp_lanes", "kbest_lanes",
                                  "lane_upload"])
def test_sweep_program_compiles_for_v5e(name, one_chip, buckets,
                                        monkeypatch):
    monkeypatch.delenv("PFDNN_PALLAS", raising=False)
    jb = get_backend("jax")
    assert jb.pallas_mode is None
    _compiles(jb, name, one_chip, buckets)


@pytest.mark.parametrize("name", ["dp_lanes", "kbest_lanes",
                                  "lane_upload"])
def test_deep_five_rail_lane_programs_compile_for_v5e(
        name, one_chip, deep_buckets, monkeypatch):
    """The lane programs of the deep deployment's S_pad 256 stores
    (mobilevit-xxs's 150-state weightless layers, resnet18) compile for
    a v5e on the compact lanes: [NB, SB, SB] blocks per lane, NB a
    handful, however deep the network."""
    monkeypatch.delenv("PFDNN_PALLAS", raising=False)
    jb = get_backend("jax")
    assert all(NB <= 8 and S == SB == 256
               for _, S, NB, SB, _ in deep_buckets), deep_buckets
    _compiles(jb, name, one_chip, deep_buckets)


def test_pallas_device_mode_is_refused_everywhere(monkeypatch):
    """Every channel that asks for the Pallas device mode raises the
    same error naming the fix — none of them falls back to the scan
    path or to interpret mode."""
    with pytest.raises(PallasDeviceUnsupported, match="float32"):
        OrchestratorConfig(pallas="device")
    with pytest.raises(PallasDeviceUnsupported, match="ROADMAP"):
        get_backend("jax-pallas")
    with pytest.raises(PallasDeviceUnsupported):
        JaxBackend(pallas="device")
    for value in ("1", "on", "device", "true"):
        monkeypatch.setenv("PFDNN_PALLAS", value)
        with pytest.raises(PallasDeviceUnsupported,
                           match="PFDNN_PALLAS"):
            get_backend("jax")
    monkeypatch.setenv("PFDNN_PALLAS", "interpret")
    assert get_backend("jax").pallas_mode == "interpret"


def _cache_probe(env: dict) -> dict:
    """Run configure_compile_cache + one jitted call in a fresh process
    (the cache settings are process-global); returns what it reports."""
    import json
    import os
    import subprocess
    import sys

    code = (
        "import json, jax, jax.numpy as jnp\n"
        "from repro.core.backend import configure_compile_cache\n"
        "path = configure_compile_cache()\n"
        "jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()\n"
        "print(json.dumps({'path': path, 'min_s': jax.config.values["
        "'jax_persistent_cache_min_compile_time_secs']}))\n")
    full = {k: v for k, v in os.environ.items()
            if k not in ("JAX_COMPILATION_CACHE_DIR",
                         "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS")}
    full.update(env, JAX_PLATFORMS="cpu",
                PYTHONPATH=os.pathsep.join(p for p in sys.path if p))
    out = subprocess.run([sys.executable, "-c", code], env=full,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compile_cache_lands_where_the_environment_says(tmp_path):
    """``$JAX_COMPILATION_CACHE_DIR`` is used as given, and every
    program is cached however fast it compiled."""
    got = _cache_probe({"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert got == {"path": str(tmp_path), "min_s": 0.0}
    assert any(tmp_path.iterdir()), "no cache entry was written"


def test_compile_cache_defaults_to_the_checkout():
    """Unset, the cache is the checkout's fixed ``.jax_cache`` — never
    a temporary, per-process or per-run path."""
    import pathlib

    import repro.core.backend as backend

    checkout = pathlib.Path(backend.__file__).resolve().parents[3]
    assert (checkout / "chip_smoke.py").exists()
    got = _cache_probe({})
    assert got["path"] == str(checkout / ".jax_cache")
