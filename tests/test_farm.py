"""Compile farm + on-disk artifact tier: crash consistency, concurrent
writers, LRU eviction, schema versioning/migration, fair-share
admission, and farm-vs-solo bit identity.

The load-bearing property mirrors ``test_service.py``: no matter which
process compiled an artifact or which tier answered the lookup
(memory, per-entry disk file, migrated schema-1 snapshot, farm
worker), the emitted schedule is bit-identical to a solo compile —
pinned here against the 23 goldens.
"""

import json
import multiprocessing
import os
import pathlib
import signal
import time

import pytest

from conftest import max_rate
from repro.core import OrchestratorConfig, compile_power_schedule
from repro.models.edge_cnn import edge_network
from repro.service import (
    ArtifactStore,
    CompileFarm,
    CompileRequest,
    CompileService,
    DiskTier,
    FairShareAdmission,
    FarmResult,
    latency_summary,
)
from repro.service.disk import DISK_SCHEMA, entry_digest

GOLDEN = json.loads(
    (pathlib.Path(__file__).parent / "golden" / "pipeline.json")
    .read_text())


def _cfg_for(key: str):
    network, frac, n_rails, policy = key.split("|")
    rate = max_rate(network) * float(frac)
    return network, rate, OrchestratorConfig(policy=policy,
                                             n_max_rails=int(n_rails))


def _request_for(key: str) -> CompileRequest:
    network, rate, cfg = _cfg_for(key)
    return CompileRequest(edge_network(network), rate, cfg,
                          network=network)


def _assert_matches_golden(key: str, sched) -> None:
    """The schedule matches the pinned pipeline golden: exact rails and
    voltage path, energies to the goldens' float tolerance (the frozen
    file predates refactors that moved the last ulp — same convention
    as ``test_pipeline_equivalence``)."""
    g = GOLDEN[key]
    assert sched is not None, f"{key}: farm returned infeasible"
    assert sched.feasible == g["feasible"]
    assert sched.e_total == pytest.approx(g["e_total"], rel=1e-9)
    assert sched.t_infer == pytest.approx(g["t_infer"], rel=1e-9)
    assert list(sched.rails) == g["rails"]
    assert [list(v) for v in sched.layer_voltages] \
        == g["layer_voltages"]


def _assert_same_schedule(a, b) -> None:
    """Bit-identical deployment artifacts — the farm-vs-solo guarantee
    (stronger than the golden-file tolerance)."""
    assert a.rails == b.rails
    assert a.layer_voltages == b.layer_voltages
    assert a.awake_banks == b.awake_banks
    assert a.e_total == b.e_total
    assert a.t_infer == b.t_infer
    assert a.e_op == b.e_op
    assert a.e_trans == b.e_trans
    assert a.e_idle == b.e_idle
    assert a.feasible == b.feasible


# ------------------------------------------------- disk tier: digests

def test_entry_digest_length_prefixed():
    """Distinct part tuples never collide by concatenation, and bytes
    hash differently from their repr."""
    assert entry_digest("ab", "c") != entry_digest("a", "bc")
    assert entry_digest("abc") != entry_digest("ab", "c")
    assert entry_digest(b"x") != entry_digest("x")
    assert entry_digest("k", 1.0) == entry_digest("k", 1.0)


# ------------------------------------- crash consistency / concurrency

def _orphaning_writer(root: str, digest: str) -> None:
    """Simulated mid-publish crash victim: writes the temp file, then
    blocks forever — the parent SIGKILLs it before the os.replace."""
    tier_dir = pathlib.Path(root) / "schedules"
    tmp = tier_dir / f"{digest}.json.{os.getpid()}.0.tmp"
    tmp.write_bytes(b'{"schema": 2, "key": ["truncat')   # partial entry
    time.sleep(600)


def test_killed_writer_mid_publish(tmp_path):
    """A writer killed between temp-write and os.replace leaves an
    orphan ``*.tmp``: a fresh store opens cleanly, every lookup ignores
    the orphan, re-publication succeeds, and the orphan is swept once
    stale."""
    root = tmp_path / "store"
    tier = DiskTier(root)
    key = ("content", "min_energy|0.01", "cfg")
    digest = tier.schedule_digest(key)

    ctx = multiprocessing.get_context("fork")
    p = ctx.Process(target=_orphaning_writer, args=(str(root), digest))
    p.start()
    tmp_name = f"{digest}.json.{p.pid}.0.tmp"
    orphan = root / "schedules" / tmp_name
    for _ in range(200):                      # wait for the temp write
        if orphan.exists():
            break
        time.sleep(0.05)
    assert orphan.exists()
    os.kill(p.pid, signal.SIGKILL)            # die before os.replace
    p.join(timeout=10)

    # fresh open: clean, orphan ignored by lookups and stats
    tier2 = DiskTier(root)
    assert tier2.get_schedule(key) is None
    assert tier2.stats()["entries"]["schedules"] == 0
    assert orphan.exists()                    # fresh orphan: not swept

    # re-publication over the orphan works and reads back
    tier2.put_schedule(key, "payload")
    assert tier2.get_schedule(key) == "payload"

    # once stale, the next open sweeps it
    old = time.time() - 7200
    os.utime(orphan, (old, old))
    tier3 = DiskTier(root)
    assert not orphan.exists()
    assert tier3.orphans_swept == 1
    assert tier3.get_schedule(key) == "payload"


def _racing_writer(root: str, payload: str, n: int) -> None:
    tier = DiskTier(root)
    key = ("content", "goal", "cfg")
    for _ in range(n):
        tier.put_schedule(key, payload)


def test_two_process_same_digest_race(tmp_path):
    """Two processes hammering the same digest: entries are
    content-addressed, so the racing payloads are byte-identical and
    last-writer-wins publication can never tear or corrupt — exactly
    one final file, no leftover temps, payload intact."""
    root = tmp_path / "store"
    DiskTier(root)                            # create layout up front
    payload = json.dumps({"rails": [0.9, 1.3], "e": 1.25e-4})
    ctx = multiprocessing.get_context("fork")
    procs = [ctx.Process(target=_racing_writer,
                         args=(str(root), payload, 60))
             for _ in range(2)]
    for p in procs:
        p.start()
    for p in procs:
        p.join(timeout=120)
        assert p.exitcode == 0

    entries = [p for p in (root / "schedules").iterdir()
               if not p.name.endswith(".tmp")]
    tmps = [p for p in (root / "schedules").iterdir()
            if p.name.endswith(".tmp")]
    assert len(entries) == 1 and not tmps
    ent = json.loads(entries[0].read_bytes().decode())
    assert ent["payload"] == payload
    assert DiskTier(root).get_schedule(("content", "goal", "cfg")) \
        == payload


# ------------------------------------------------- eviction + schema

def test_lru_eviction_oldest_first(tmp_path):
    tier = DiskTier(tmp_path / "store", max_entries=2)
    keys = [("c", f"goal{i}", "cfg") for i in range(4)]
    for i, key in enumerate(keys):
        tier.put_schedule(key, f"payload{i}")
        # deterministic mtime order regardless of fs timestamp
        # granularity
        path = tier._path("schedules", tier.schedule_digest(key),
                          ".json")
        os.utime(path, (1000.0 + i, 1000.0 + i))
    # a read bumps recency: key 0 becomes the newest
    now = time.time()
    assert tier.get_schedule(keys[0]) == "payload0"
    path0 = tier._path("schedules", tier.schedule_digest(keys[0]),
                       ".json")
    assert path0.stat().st_mtime >= now - 5

    assert tier.evict_to_budget() == 2
    assert tier.get_schedule(keys[0]) == "payload0"   # recently read
    assert tier.get_schedule(keys[3]) == "payload3"   # newest write
    assert tier.get_schedule(keys[1]) is None          # oldest: evicted
    assert tier.get_schedule(keys[2]) is None
    assert tier.stats()["evictions"]["schedules"] == 2
    assert tier.stats()["entries"]["schedules"] == 2


def test_unknown_newer_schema_refuses(tmp_path):
    root = tmp_path / "store"
    DiskTier(root)
    (root / "STORE_META.json").write_text(json.dumps({"schema": 99}))
    with pytest.raises(ValueError, match="schema 99"):
        DiskTier(root)
    with pytest.raises(ValueError, match="schema 99"):
        ArtifactStore(disk_path=root)


def test_meta_pins_current_schema(tmp_path):
    root = tmp_path / "store"
    DiskTier(root)
    meta = json.loads((root / "STORE_META.json").read_text())
    assert meta["schema"] == DISK_SCHEMA == 2
    assert DiskTier(root).schema == DISK_SCHEMA   # reopen accepts


# ------------------------------------------------- store: disk tier

@pytest.fixture(scope="module")
def shared_dir(tmp_path_factory):
    """A disk store populated by one cold inline farm run over every
    golden config, submitted by three tenants — the shared-warm state
    the cross-process tests start from."""
    root = tmp_path_factory.mktemp("farm") / "store"
    farm = CompileFarm(root, n_workers=0, batch_size=8)
    tenants = ("teamA", "teamB", "teamC")
    uid_to_key = {}
    for i, key in enumerate(sorted(GOLDEN)):
        (uid,) = farm.submit(tenants[i % 3], [_request_for(key)])
        uid_to_key[uid] = key
    results = farm.drain()
    farm.close()
    return root, {uid_to_key[uid]: res for uid, res in results.items()}


@pytest.mark.parametrize("key", sorted(GOLDEN))
def test_farm_results_match_goldens(key, shared_dir):
    """Every schedule the farm emitted is bit-identical to the solo
    pipeline golden, and carries its provenance."""
    _, results = shared_dir
    res = results[key]
    assert res.error is None
    assert isinstance(res, FarmResult) and res.latency_s >= 0
    _assert_matches_golden(key, res.value)


@pytest.mark.parametrize("key", sorted(GOLDEN)[::5])
def test_farm_vs_solo_bit_identical(key, shared_dir):
    """The farm's schedule is bit-identical to a solo
    ``compile_power_schedule`` of the same point — every field, not
    just to golden tolerance."""
    _, results = shared_dir
    network, rate, cfg = _cfg_for(key)
    solo = compile_power_schedule(edge_network(network), rate, cfg=cfg,
                                  network=network)
    _assert_same_schedule(solo, results[key].value)


@pytest.mark.parametrize("key", sorted(GOLDEN)[::5])
def test_disk_warm_store_matches_goldens(key, shared_dir):
    """A *fresh* store over the farm's directory (a new process, as far
    as the tier can tell) serves the same configs shared-warm: the
    schedule streams in as a disk hit and stays bit-identical."""
    root, _ = shared_dir
    svc = CompileService(store=ArtifactStore(disk_path=root))
    network, rate, cfg = _cfg_for(key)
    sched = svc.compile(edge_network(network), rate, cfg=cfg,
                        network=network)
    _assert_matches_golden(key, sched)
    stats = svc.store.stats()
    assert stats["disk_hits"]["schedule"] == 1
    assert stats["hits"]["schedule"] == 1


def test_disk_warm_solve_parity(shared_dir):
    """With the schedule cache disabled, a fresh store still warm-starts
    the full solve from the disk tier's tables (master/transition/
    pruning disk hits) and reproduces the golden exactly."""
    root, _ = shared_dir
    key = "squeezenet1.1|0.9|2|pfdnn"   # a full-DP policy: uses tables
    svc = CompileService(store=ArtifactStore(disk_path=root),
                         use_schedule_cache=False)
    network, rate, cfg = _cfg_for(key)
    sched = svc.compile(edge_network(network), rate, cfg=cfg,
                        network=network)
    _assert_matches_golden(key, sched)
    dh = svc.store.stats()["disk_hits"]
    assert dh["master"] >= 1
    assert dh["transition"] >= 1


def test_store_clear_streams_back_from_disk(tmp_path):
    key = sorted(GOLDEN)[0]
    network, rate, cfg = _cfg_for(key)
    svc = CompileService(disk_path=tmp_path / "store")
    first = svc.compile(edge_network(network), rate, cfg=cfg,
                        network=network)
    svc.store.clear()                 # memory gone, disk untouched
    again = svc.compile(edge_network(network), rate, cfg=cfg,
                        network=network)
    _assert_matches_golden(key, first)
    _assert_matches_golden(key, again)
    assert svc.store.stats()["disk_hits"]["schedule"] == 1


def test_deferred_publication_batches_and_dedups(tmp_path):
    store = ArtifactStore(disk_path=tmp_path / "store")
    sched_dir = tmp_path / "store" / "schedules"
    with store.deferred_publication():
        store.put_schedule(("c", "g1", "cfg"), None)
        store.put_schedule(("c", "g1", "cfg"), None)   # dedup
        store.put_schedule(("c", "g2", "cfg"), None)
        with store.deferred_publication():             # nested: no-op
            store.put_schedule(("c", "g3", "cfg"), None)
        assert list(sched_dir.iterdir()) == []         # still buffered
    files = [p for p in sched_dir.iterdir()
             if not p.name.endswith(".tmp")]
    assert len(files) == 3
    # memory answered throughout; nothing re-published on read
    assert store.schedule(("c", "g3", "cfg")) is not None


def test_store_eviction_budget(tmp_path):
    store = ArtifactStore(disk_path=tmp_path / "store",
                          max_disk_entries=1)
    for i in range(3):
        store.put_schedule(("c", f"g{i}", "cfg"), None)
    store.flush_disk()
    stats = store.stats()["disk"]
    assert stats["entries"]["schedules"] == 1
    assert sum(stats["evictions"].values()) == 2


# ------------------------------------- schema-1 snapshot migration

def test_snapshot_migration_roundtrip(tmp_path):
    """A pre-PR monolithic ``save()`` snapshot (schema 1) loads into a
    disk-backed store, republishes as per-entry schema-2 files, and a
    *fresh* store over that directory serves the entries shared-warm,
    bit-identical to the golden."""
    key = "squeezenet1.1|0.9|2|pfdnn"   # full-DP: snapshot gets tables
    network, rate, cfg = _cfg_for(key)
    # a memory-only service, exactly what a pre-PR deployment ran
    svc = CompileService()
    svc.compile(edge_network(network), rate, cfg=cfg, network=network)
    snap = tmp_path / "snapshot.npz"
    svc.store.save(snap)

    root = tmp_path / "store"
    migrated = ArtifactStore(disk_path=root).load(snap)
    tier_stats = migrated.stats()["disk"]
    assert tier_stats["entries"]["schedules"] >= 1
    assert tier_stats["entries"]["masters"] >= 1
    assert tier_stats["entries"]["transitions"] >= 1

    fresh = CompileService(store=ArtifactStore(disk_path=root))
    sched = fresh.compile(edge_network(network), rate, cfg=cfg,
                          network=network)
    _assert_matches_golden(key, sched)
    assert fresh.store.stats()["disk_hits"]["schedule"] == 1


def test_unknown_snapshot_version_refuses(tmp_path):
    import numpy as np

    snap = tmp_path / "bad.npz"
    manifest = np.frombuffer(json.dumps({"version": 9}).encode(),
                             dtype=np.uint8)
    np.savez_compressed(snap, manifest=manifest)
    with pytest.raises(ValueError, match="version 9"):
        ArtifactStore().load(snap)


# ------------------------------------------------- fair-share admission

def test_fair_share_round_robin_interleave():
    adm = FairShareAdmission()
    for i in range(6):
        adm.push("A", f"A{i}")
    for i in range(2):
        adm.push("B", f"B{i}")
    for i in range(2):
        adm.push("C", f"C{i}")
    batch = adm.next_batch(6)
    # one per tenant per turn, FIFO within tenant
    assert batch == ["A0", "B0", "C0", "A1", "B1", "C1"]
    assert adm.next_batch(10) == ["A2", "A3", "A4", "A5"]
    assert adm.pending() == 0


def test_fair_share_late_tenant_admitted_next_batch():
    """A late-arriving tenant is not starved behind an earlier burst:
    it gets its fair share of the very next batch."""
    adm = FairShareAdmission()
    for i in range(100):
        adm.push("burst", f"b{i}")
    assert adm.next_batch(4) == ["b0", "b1", "b2", "b3"]
    adm.push("interactive", "i0")
    nxt = adm.next_batch(4)
    assert "i0" in nxt
    assert nxt.count("i0") == 1 and len(nxt) == 4


def test_latency_summary_per_tenant():
    def res(tenant, lat):
        return FarmResult(uid=0, tenant=tenant, value=None,
                          latency_s=lat, worker=0, batch_id=0,
                          batch_wall_s=lat)

    rows = [res("A", s) for s in (0.1, 0.2, 0.3)] \
        + [res("B", s) for s in (1.0, 2.0)]
    summary = latency_summary(rows)
    assert summary["fleet"]["n"] == 5
    assert summary["fleet"]["max_s"] == 2.0
    assert summary["tenants"]["A"]["p50_s"] == pytest.approx(0.2)
    assert summary["tenants"]["B"]["n"] == 2


# ------------------------------------------------- farm end-to-end

def test_farm_inline_repeat_traffic_hits_cache(tmp_path):
    """Repeat requests across tenants answer from the shared schedule
    cache (hits counted), and every copy is bit-identical."""
    key = "squeezenet1.1|0.9|2|pfdnn"
    farm = CompileFarm(tmp_path / "store", n_workers=0, batch_size=4)
    uids_a = farm.submit("A", [_request_for(key)] * 3)
    uids_b = farm.submit("B", [_request_for(key)] * 3)
    results = farm.drain()
    farm.close()
    for uid in uids_a + uids_b:
        _assert_matches_golden(key, results[uid].value)
    counters = farm.counters()
    # batch 1 solves once (in-batch duplicates dedup to the same solve);
    # batch 2 answers entirely from the schedule cache
    assert counters["hits"]["schedule"] >= 2
    assert counters["misses"]["schedule"] >= 1
    assert farm.n_batches >= 2


def test_farm_validates_arguments(tmp_path):
    with pytest.raises(ValueError, match="n_workers"):
        CompileFarm(tmp_path / "s", n_workers=-1)
    with pytest.raises(ValueError, match="batch_size"):
        CompileFarm(tmp_path / "s", batch_size=0)
    (tmp_path / "bad").mkdir()
    (tmp_path / "bad" / "STORE_META.json").write_text('{"schema": 99}')
    with pytest.raises(ValueError, match="schema 99"):
        CompileFarm(tmp_path / "bad")   # fails at construction


def test_farm_places_jax_workers_on_the_chips(tmp_path, monkeypatch):
    """On a TPU host a jax farm refuses a second worker (a jax process
    opens every local chip) and refuses to start from a parent that
    already holds the chips; off TPU, or on numpy, nothing is refused.
    Every refusal comes before a worker process is spawned."""
    import repro.service.farm as farm_mod

    monkeypatch.setattr(farm_mod, "local_tpu_chips", lambda: 1)
    monkeypatch.setattr(farm_mod, "jax_backend_initialized", lambda: False)
    with pytest.raises(ValueError, match="n_workers=2"):
        CompileFarm(tmp_path / "s", n_workers=2, backend="jax").start()
    monkeypatch.setenv("PFDNN_BACKEND", "jax")     # the default backend
    with pytest.raises(ValueError, match="n_workers=2"):
        CompileFarm(tmp_path / "s", n_workers=2).start()
    monkeypatch.setattr(farm_mod, "jax_backend_initialized", lambda: True)
    with pytest.raises(RuntimeError, match="already initialised jax"):
        CompileFarm(tmp_path / "s", n_workers=1, backend="jax").start()
    # numpy workers never touch the chips; no chips, nothing to place
    CompileFarm(tmp_path / "s", n_workers=4,
                backend="numpy")._check_chip_placement()
    monkeypatch.setattr(farm_mod, "local_tpu_chips", lambda: 0)
    CompileFarm(tmp_path / "s", n_workers=4,
                backend="jax")._check_chip_placement()


def test_farm_submit_rejects_jax_requests_on_a_non_jax_farm(tmp_path):
    key = "squeezenet1.1|0.9|2|pfdnn"
    network, rate, cfg = _cfg_for(key)
    req = CompileRequest(edge_network(network), rate,
                         OrchestratorConfig(policy=cfg.policy,
                                            backend="jax"),
                         network=network)
    farm = CompileFarm(tmp_path / "s", n_workers=0, backend="numpy")
    with pytest.raises(ValueError, match="CompileFarm\\(backend"):
        farm.submit("A", [req])
    assert farm.pending() == 0
    CompileFarm(tmp_path / "s", n_workers=0, backend="jax").submit(
        "A", [req])


def test_farm_cross_process_shared_warm(tmp_path):
    """The real thing: a 2-worker spawn farm compiles cold; a second
    farm with *fresh worker processes* over the same directory answers
    shared-warm from cross-process disk hits — bit-identical to the
    goldens both times."""
    keys = ["squeezenet1.1|0.9|2|pfdnn",
            "mobilenetv3-small|0.85|2|pfdnn"]
    root = tmp_path / "store"

    def run_farm():
        with CompileFarm(root, n_workers=2, batch_size=2) as farm:
            uid_to_key = {}
            for tenant, key in zip(("A", "B", "A", "B"), keys * 2):
                (uid,) = farm.submit(tenant, [_request_for(key)])
                uid_to_key[uid] = key
            results = farm.drain()
            counters = farm.counters()
        return {uid_to_key[u]: r for u, r in results.items()}, counters

    cold, _ = run_farm()
    warm, warm_counters = run_farm()           # fresh processes
    for key in keys:
        _assert_matches_golden(key, cold[key].value)
        _assert_matches_golden(key, warm[key].value)
    for res in list(cold.values()) + list(warm.values()):
        assert res.error is None
    # cross-process sharing: the second farm never saw these compiles,
    # yet its workers answered from the first farm's published entries
    assert warm_counters["disk_hits"]["schedule"] >= 1


# ------------------------------------------------- service lifecycle

def test_service_close_and_context_manager(tmp_path):
    key = sorted(GOLDEN)[0]
    network, rate, cfg = _cfg_for(key)
    with CompileService(disk_path=tmp_path / "store") as svc:
        sched = svc.compile(edge_network(network), rate, cfg=cfg,
                            network=network)
        _assert_matches_golden(key, sched)
    svc.close()                        # idempotent
    # the service stays usable after close (sync path needs no pool)
    again = svc.compile(edge_network(network), rate, cfg=cfg,
                        network=network)
    _assert_matches_golden(key, again)


def test_service_rejects_store_and_disk_path(tmp_path):
    with pytest.raises(ValueError, match="not both"):
        CompileService(store=ArtifactStore(),
                       disk_path=tmp_path / "store")


def test_compile_accepts_store_path(tmp_path):
    """``compile_power_schedule(store=<path>)`` builds the disk-backed
    store inline — the one-liner migration for scripts that never
    touch the service API."""
    key = sorted(GOLDEN)[0]
    network, rate, cfg = _cfg_for(key)
    root = tmp_path / "store"
    first = compile_power_schedule(edge_network(network), rate, cfg=cfg,
                                   network=network, store=str(root))
    _assert_matches_golden(key, first)
    assert (root / "STORE_META.json").exists()
    again = compile_power_schedule(edge_network(network), rate, cfg=cfg,
                                   network=network, store=root)
    _assert_matches_golden(key, again)
    with pytest.raises(TypeError, match="store="):
        compile_power_schedule(edge_network(network), rate, cfg=cfg,
                               network=network, store=42)
