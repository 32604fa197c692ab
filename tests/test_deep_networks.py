"""The deep networks at five rails on the jax backend: mobilevit-xxs
(70 layers, gated RRAM states under its weightless layers, 150-state
layers in S_pad 256 lane stores) and resnet18, compiled together by one
``compile_many`` on jax (CPU) and on numpy, give the same schedules bit
for bit, and the certifier passes each."""

import dataclasses

import pytest

from conftest import max_rate
from repro.analysis.certify import certify
from repro.core import OrchestratorConfig
from repro.core.backend import JaxBackend, get_backend
from repro.models.edge_cnn import edge_network
from repro.service import CompileRequest, CompileService, MinEnergy

pytest.importorskip("jax")

NETWORKS = ("mobilevit-xxs", "resnet18")
INPUT_HW = 64


def _compile(backend: str):
    cfg = OrchestratorConfig(policy="pfdnn", n_max_rails=5,
                             backend=backend)
    with CompileService() as svc:
        scheds = svc.compile_many([
            CompileRequest(edge_network(n, INPUT_HW), cfg=cfg, network=n,
                           goal=MinEnergy(rate_hz=0.6 * max_rate(n)))
            for n in NETWORKS])
        stores = [(bs.n_blocks, bs._t_op.shape[2])
                  for bs in svc.store.stack_caches.buckets.values()]
    return scheds, stores


def test_deep_networks_at_five_rails_match_numpy_and_certify(monkeypatch):
    # the k-best rounds take the host kernels on the CPU: a jitted k-best
    # at S_pad 256 runs minutes on XLA's CPU backend, and its compact
    # lanes are pinned bit for bit in test_compact_lanes; the DP rounds
    # of the wide stores stay on the jitted lane programs
    monkeypatch.setattr(JaxBackend, "_KBEST_JIT_MIN_WORK", 1 << 40)
    before = dict(get_backend("jax").io_stats)
    ours, stores = _compile("jax")
    ref, _ = _compile("numpy")
    for net, a, b in zip(NETWORKS, ours, ref):
        assert a is not None and b is not None, net
        mine, theirs = dataclasses.asdict(a), dataclasses.asdict(b)
        mine.pop("solver_stats")
        theirs.pop("solver_stats")
        assert mine == theirs, net
        specs = edge_network(net, INPUT_HW)
        cert = certify(a, specs, n_max_rails=5)
        assert cert.ok, cert.summary()
    # every lane store holds at most four distinct blocks a lane, and
    # the widest stores are S_pad 256
    assert max(s for _, s in stores) == 256
    assert all(nb <= 4 for nb, _ in stores)
    after = get_backend("jax").io_stats
    assert after["lane_blocks"] > before["lane_blocks"]
    assert after["kernel_dispatches"] > before["kernel_dispatches"]
