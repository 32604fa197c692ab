"""Tests for the verification suite (``repro.analysis``): the
independent schedule certifier + its mutation-detection fixture, the
artifact-store audit walker, the PowerSchedule schema gate, the
determinism linter, and the lock-order analyzer."""

import dataclasses
import json
import pathlib
import textwrap
import threading

import pytest

from conftest import max_rate
from repro.analysis import lockcheck
from repro.analysis.certify import (
    DEADLINE_VIOLATED,
    ENERGY_MISMATCH,
    ILLEGAL_TRANSITION,
    LEDGER_DRIFT,
    RAIL_COUNT_EXCEEDED,
    certify,
    certify_store,
)
from repro.analysis.lint_determinism import (
    apply_baseline,
    lint_source,
    lint_tree,
    load_baseline,
    save_baseline,
)
from repro.core import OrchestratorConfig, compile_power_schedule
from repro.core.schedule import PowerSchedule, SCHEDULE_SCHEMA
from repro.hw.dvfs import V_GATED
from repro.hw.edge40nm import EDGE40NM_DEFAULT as ACC
from repro.models.edge_cnn import edge_network
from repro.perfmodel import characterize_network
from repro.service.store import ArtifactStore

NETWORK = "squeezenet1.1"
N_RAILS = 3


@pytest.fixture(scope="module")
def specs():
    return edge_network(NETWORK)


@pytest.fixture(scope="module")
def golden_sched(specs):
    """One representative compiled artifact (the full 23-case × 3-backend
    sweep is the CI ``analysis`` job, not a unit test)."""
    sched = compile_power_schedule(
        specs, max_rate(NETWORK) * 0.85,
        cfg=OrchestratorConfig(policy="pfdnn", n_max_rails=N_RAILS),
        network=NETWORK)
    assert sched is not None and sched.feasible
    return sched


# ===================================================== certifier: clean

@pytest.mark.parametrize("policy", ["baseline", "greedy_gating", "pfdnn"])
def test_certify_clean_policies(specs, policy):
    sched = compile_power_schedule(
        specs, max_rate(NETWORK) * 0.85,
        cfg=OrchestratorConfig(policy=policy, n_max_rails=N_RAILS),
        network=NETWORK)
    assert sched is not None
    cert = certify(sched, specs, acc=ACC, n_max_rails=N_RAILS)
    assert cert.ok, cert.summary()
    assert cert.violations == []
    # re-derivation agrees with the ledger to tolerance
    assert cert.derived["e_total"] == pytest.approx(sched.e_total,
                                                   rel=1e-9)
    assert cert.derived["t_infer"] == pytest.approx(sched.t_infer,
                                                   rel=1e-9)


def test_certify_zero_slack_min_latency_artifact(golden_sched, specs):
    """A MinLatency artifact records ``t_max = t_infer`` and no idle
    energy; the re-derived latency may sit an ulp below it, which
    prices a ~1e-21 J idle interval — not a ledger mismatch."""
    from repro.core import compile as compile_goal
    from repro.core.goals import MinLatency

    budget = 1.3 * (golden_sched.e_op + golden_sched.e_trans)
    sched = compile_goal(
        specs, MinLatency(energy_budget_j=budget),
        cfg=OrchestratorConfig(policy="pfdnn", n_max_rails=N_RAILS),
        network=NETWORK)
    assert sched.e_idle == 0.0 and sched.t_max == sched.t_infer
    cert = certify(sched, specs, acc=ACC, n_max_rails=N_RAILS)
    assert cert.ok, cert.summary()
    # a real idle-energy error is still flagged at the same tolerance
    bad = dataclasses.replace(sched, e_idle=1e-6 * sched.e_total,
                              e_total=sched.e_total * (1 + 1e-6))
    kinds = {v.kind for v in certify(bad, specs, acc=ACC,
                                     n_max_rails=N_RAILS).violations}
    assert ENERGY_MISMATCH in kinds


def test_certify_dual_bound(golden_sched, specs):
    cert = certify(golden_sched, specs, acc=ACC, n_max_rails=N_RAILS)
    assert cert.dual is not None
    # weak duality: the bound never exceeds the recorded energy
    assert cert.dual.gap_abs >= -1e-9 * cert.dual.energy
    assert cert.dual.bound <= cert.dual.energy + 1e-12
    assert 0.0 <= cert.dual.gap_rel < 0.25   # pfdnn sits near the envelope


def test_certify_no_dual_skips(golden_sched, specs):
    cert = certify(golden_sched, specs, acc=ACC, dual=False)
    assert cert.ok and cert.dual is None


def test_certificate_round_trips(golden_sched, specs):
    cert = certify(golden_sched, specs, acc=ACC, n_max_rails=N_RAILS)
    d = cert.to_dict()
    assert d["ok"] and d["network"] == NETWORK
    json.dumps(d)            # serializable as-is
    assert "PASS" in cert.summary()


# ================================================= certifier: mutations

def _weighted_layer(specs):
    costs = characterize_network(specs, ACC)
    for i, c in enumerate(costs):
        if c.weight_bytes != 0 or c.cycles[2] > 0:
            return i
    raise AssertionError("network has no weighted layer")


def _off_rail_level(sched):
    for v in ACC.levels():
        if v not in sched.rails:
            return v
    raise AssertionError("rail set covers the whole menu")


def _set_volt(sched, layer, domain, value):
    rows = [list(v) for v in sched.layer_voltages]
    rows[layer][domain] = value
    return dataclasses.replace(
        sched, layer_voltages=[tuple(r) for r in rows])


# seeded corruption -> the violation kind the certifier must emit
MUTATIONS = [
    ("shaved_deadline",
     lambda s, specs: dataclasses.replace(s, t_max=s.t_infer * 0.5),
     DEADLINE_VIOLATED),
    ("off_rail_voltage",
     lambda s, specs: _set_volt(s, 0, 0, _off_rail_level(s)),
     RAIL_COUNT_EXCEEDED),
    ("off_menu_voltage",
     lambda s, specs: _set_volt(s, 0, 0, 0.123),
     ILLEGAL_TRANSITION),
    ("gated_compute",
     lambda s, specs: _set_volt(s, 0, 0, V_GATED),
     ILLEGAL_TRANSITION),
    ("gated_rram_weighted_layer",
     lambda s, specs: _set_volt(s, _weighted_layer(specs), 2, V_GATED),
     ILLEGAL_TRANSITION),
    ("halved_e_trans",
     lambda s, specs: dataclasses.replace(s, e_trans=s.e_trans * 0.5),
     ENERGY_MISMATCH),
    ("inflated_e_op",
     lambda s, specs: dataclasses.replace(s, e_op=s.e_op * (1 + 1e-5)),
     ENERGY_MISMATCH),
    ("broken_e_total_sum",
     lambda s, specs: dataclasses.replace(
         s, e_total=s.e_total * (1 + 1e-5)),
     LEDGER_DRIFT),
    ("bumped_awake_banks",
     lambda s, specs: dataclasses.replace(
         s, awake_banks=[s.awake_banks[0] + 1] + list(s.awake_banks[1:])),
     LEDGER_DRIFT),
    ("bumped_rail_switches",
     lambda s, specs: dataclasses.replace(
         s, n_rail_switches=s.n_rail_switches + 1),
     LEDGER_DRIFT),
    ("flipped_idle_flag",
     lambda s, specs: dataclasses.replace(
         s, z_active_idle=1 - int(s.z_active_idle)),
     LEDGER_DRIFT),
    ("false_infeasibility_claim",
     lambda s, specs: dataclasses.replace(s, feasible=False),
     LEDGER_DRIFT),
]


@pytest.mark.parametrize("name,mutate,expected",
                         MUTATIONS, ids=[m[0] for m in MUTATIONS])
def test_mutation_is_flagged(golden_sched, specs, name, mutate, expected):
    mutant = mutate(golden_sched, specs)
    cert = certify(mutant, specs, acc=ACC, n_max_rails=N_RAILS)
    assert not cert.ok, f"{name}: corruption certified clean"
    kinds = {v.kind for v in cert.violations}
    assert expected in kinds, \
        f"{name}: expected {expected}, got {sorted(kinds)}"


def test_clean_schedule_has_no_false_positives(golden_sched, specs):
    """The mutation fixture is only meaningful if the unmutated artifact
    certifies clean under the exact same call."""
    cert = certify(golden_sched, specs, acc=ACC, n_max_rails=N_RAILS)
    assert cert.ok and not cert.violations


def test_certify_wrong_layer_count(golden_sched, specs):
    mutant = dataclasses.replace(
        golden_sched,
        layer_voltages=golden_sched.layer_voltages[:-1],
        awake_banks=golden_sched.awake_banks[:-1])
    cert = certify(mutant, specs, acc=ACC)
    assert not cert.ok
    assert cert.violations[0].kind == LEDGER_DRIFT
    assert "layers" in cert.violations[0].where


def test_certify_calibrated_artifact_needs_cost_model(golden_sched, specs):
    mutant = dataclasses.replace(golden_sched, cost_model="abc123")
    with pytest.raises(ValueError, match="cost_model"):
        certify(mutant, specs, acc=ACC)


# ====================================================== store audit

def test_certify_store_clean(tmp_path, golden_sched):
    store = ArtifactStore(disk_path=tmp_path / "tier")
    store.put_schedule(("content", "goal", "cfg"), golden_sched)
    store.put_schedule(("content2", "goal", "cfg"), None)  # sentinel
    audit = certify_store(store)
    assert audit["ok"], audit["problems"]
    # 2 memory entries + 2 disk entries
    assert audit["entries"] == 4


def test_certify_store_flags_key_content_mismatch(tmp_path, golden_sched):
    store = ArtifactStore(disk_path=tmp_path / "tier")
    store.put_schedule(("content", "goal", "cfg"), golden_sched)
    sched_dir = tmp_path / "tier" / "schedules"
    entry_path = next(sched_dir.glob("*.json"))
    ent = json.loads(entry_path.read_text())
    ent["key"] = ["tampered", "goal", "cfg"]
    entry_path.write_text(json.dumps(ent))
    audit = certify_store(tmp_path / "tier")    # path form
    assert not audit["ok"]
    assert any("key↔content mismatch" in p["detail"]
               for p in audit["problems"])


def test_certify_store_flags_ledger_drift(tmp_path, golden_sched):
    store = ArtifactStore(disk_path=tmp_path / "tier")
    broken = dataclasses.replace(golden_sched,
                                 e_total=golden_sched.e_total * 2)
    store.put_schedule(("content", "goal", "cfg"), broken)
    audit = certify_store(store)
    assert not audit["ok"]
    assert any("ledger drift" in p["detail"] for p in audit["problems"])


def test_certify_store_flags_unparseable_payload(tmp_path):
    root = tmp_path / "tier"
    store = ArtifactStore(disk_path=root)
    store.put_schedule(("content", "goal", "cfg"), None)
    entry_path = next((root / "schedules").glob("*.json"))
    ent = json.loads(entry_path.read_text())
    ent["payload"] = "{not json"
    entry_path.write_text(json.dumps(ent))
    audit = certify_store(root)
    assert not audit["ok"]
    assert any("does not parse" in p["detail"] for p in audit["problems"])


# ============================================= PowerSchedule schema gate

def test_schedule_json_round_trip_carries_schema(golden_sched):
    d = json.loads(golden_sched.to_json())
    assert d["schema"] == SCHEDULE_SCHEMA
    again = PowerSchedule.from_json(golden_sched.to_json())
    assert again == golden_sched


def test_schedule_legacy_payload_still_loads(golden_sched):
    d = json.loads(golden_sched.to_json())
    del d["schema"]                       # pre-schema snapshot
    again = PowerSchedule.from_json(json.dumps(d))
    assert again == golden_sched


def test_schedule_refuses_newer_schema(golden_sched):
    d = json.loads(golden_sched.to_json())
    d["schema"] = 99
    with pytest.raises(ValueError,
                       match="refusing to misread a newer layout"):
        PowerSchedule.from_json(json.dumps(d))


def test_schedule_rejects_unknown_field(golden_sched):
    d = json.loads(golden_sched.to_json())
    d["surprise"] = 1
    with pytest.raises(ValueError, match="unknown field"):
        PowerSchedule.from_json(json.dumps(d))


def test_schedule_rejects_missing_field(golden_sched):
    d = json.loads(golden_sched.to_json())
    del d["e_total"]
    with pytest.raises(ValueError, match="missing"):
        PowerSchedule.from_json(json.dumps(d))


def test_schedule_rejects_non_object():
    with pytest.raises(ValueError):
        PowerSchedule.from_json("[1, 2, 3]")


# ==================================================== determinism linter

def test_lint_unseeded_rng():
    src = textwrap.dedent("""\
        import numpy as np
        x = np.random.rand(3)
        rng = np.random.default_rng()
        ok = np.random.default_rng(0)
    """)
    rules = [f.rule for f in lint_source(src, "m.py")]
    assert rules == ["unseeded-rng", "unseeded-rng"]


def test_lint_wall_clock_and_alias():
    src = textwrap.dedent("""\
        import time as t
        from time import perf_counter
        a = t.time()
        b = perf_counter()
    """)
    findings = lint_source(src, "m.py")
    assert [f.rule for f in findings] == ["wall-clock", "wall-clock"]
    assert findings[0].line == 3


def test_lint_set_iteration_and_float_accum():
    src = textwrap.dedent("""\
        s = {1, 2, 3}
        out = [x for x in {1, 2}]
        for x in set(s) | {4}:
            pass
        tot = sum({0.1, 0.2})
        fine = sorted({1, 2})
        also_fine = {x for x in {1, 2}}
    """)
    rules = sorted(f.rule for f in lint_source(src, "m.py"))
    assert rules == ["float-accum", "set-iteration", "set-iteration"]


def test_lint_inline_suppression():
    src = "import time\nx = time.time()  # pfdnn: allow(wall-clock)\n"
    assert lint_source(src, "m.py") == []
    # wrong rule in the allow -> still flagged
    src2 = "import time\nx = time.time()  # pfdnn: allow(unseeded-rng)\n"
    assert len(lint_source(src2, "m.py")) == 1


def test_lint_baseline_round_trip(tmp_path):
    tree = tmp_path / "pkg"
    tree.mkdir()
    (tree / "a.py").write_text("import time\nx = time.time()\n")
    findings = lint_tree(tree)
    assert len(findings) == 1
    bl_path = tmp_path / "baseline.json"
    save_baseline(bl_path, findings)
    baseline = load_baseline(bl_path)
    new, suppressed = apply_baseline(lint_tree(tree), baseline)
    assert new == [] and len(suppressed) == 1
    # a fresh finding is NOT suppressed by the old baseline
    (tree / "a.py").write_text(
        "import time\nx = time.time()\ny = time.monotonic()\n")
    new, suppressed = apply_baseline(lint_tree(tree), baseline)
    assert len(new) == 1 and "monotonic" in new[0].message


def test_repo_lint_is_clean_under_committed_baseline():
    root = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"
    baseline = load_baseline(
        pathlib.Path(__file__).parent / "determinism_baseline.json")
    new, _ = apply_baseline(lint_tree(root), baseline)
    assert new == [], [str(f) for f in new]


# ====================================================== lock-order check

@pytest.fixture
def recording():
    was = lockcheck.enabled()
    lockcheck.enable()
    lockcheck.reset()
    yield
    lockcheck.reset()
    if not was:
        lockcheck.disable()


def test_make_lock_plain_when_disabled():
    if lockcheck.enabled():
        pytest.skip("suite running under PFDNN_LOCKCHECK=1")
    lock = lockcheck.make_lock("x._lock")
    assert isinstance(lock, type(threading.Lock()))


def test_nested_acquire_records_edge(recording):
    a = lockcheck.make_lock("a._lock")
    b = lockcheck.make_lock("b._lock")
    with a:
        with b:
            pass
    g = lockcheck.graph()
    assert g["edges"] == {"a._lock -> b._lock": 1}
    assert lockcheck.assert_clean()["ok"]


def test_opposite_orders_form_cycle(recording):
    a = lockcheck.make_lock("a._lock")
    b = lockcheck.make_lock("b._lock")
    with a:
        with b:
            pass
    with b:
        with a:
            pass
    report = lockcheck.check()
    assert report["cycles"] == [["a._lock", "b._lock"]]
    with pytest.raises(lockcheck.LockOrderError):
        lockcheck.assert_clean()


def test_reentrant_self_acquire_is_not_an_edge(recording):
    r = lockcheck.make_lock("r._lock", reentrant=True)
    with r:
        with r:
            pass
    assert lockcheck.graph()["edges"] == {}


def test_barrier_hazard(recording):
    a = lockcheck.make_lock("a._lock")
    lockcheck.barrier("clear")           # nothing held: fine
    with a:
        lockcheck.barrier("compile_many")
    report = lockcheck.check()
    assert report["hazards"] == [
        {"barrier": "compile_many", "held": ["a._lock"]}]
    assert not report["ok"]


def test_edges_recorded_across_threads(recording):
    a = lockcheck.make_lock("a._lock")
    b = lockcheck.make_lock("b._lock")

    def worker():
        with a:
            with b:
                pass

    ts = [threading.Thread(target=worker) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert lockcheck.graph()["edges"] == {"a._lock -> b._lock": 4}


def test_dump_and_merge(recording, tmp_path):
    a = lockcheck.make_lock("a._lock")
    b = lockcheck.make_lock("b._lock")
    with a:
        with b:
            pass
    path = tmp_path / "graph.jsonl"
    lockcheck.dump(path)
    lockcheck.dump(path)                 # second "process"
    merged = lockcheck.merge_dumps(path)
    assert merged["edges"] == {("a._lock", "b._lock"): 2}
    assert merged["locks"] == ["a._lock", "b._lock"]
    assert merged["hazards"] == []


def test_find_cycles_three_node():
    edges = [("a", "b"), ("b", "c"), ("c", "a"), ("c", "d")]
    assert lockcheck.find_cycles(edges) == [["a", "b", "c"]]
    assert lockcheck.find_cycles([("a", "b"), ("b", "c")]) == []


def test_static_nesting_scan(tmp_path):
    (tmp_path / "mod.py").write_text(textwrap.dedent("""\
        class C:
            def f(self):
                with self._lock:
                    with self.agg_lock:
                        pass

            def g(self):
                with self._lock:
                    def inner():
                        with self.agg_lock:   # new frame: not nested
                            pass
                    return inner
    """))
    nests = lockcheck.static_lock_nesting(tmp_path)
    assert [(n.outer, n.inner) for n in nests] == \
        [("mod._lock", "mod.agg_lock")]


def test_cross_check_coverage_and_cycles(tmp_path):
    (tmp_path / "m.py").write_text(textwrap.dedent("""\
        def f(self):
            with self._lock:
                with self.agg_lock:
                    pass
    """))
    nests = lockcheck.static_lock_nesting(tmp_path)
    covered = lockcheck.cross_check(
        nests, [("m._lock", "m.agg_lock")])
    assert covered["ok"] and covered["uncovered"] == []
    uncovered = lockcheck.cross_check(nests, [])
    assert uncovered["ok"]               # coverage gaps are non-fatal
    assert len(uncovered["uncovered"]) == 1
    # opposite textual orders are a static inversion: fatal
    (tmp_path / "n.py").write_text(textwrap.dedent("""\
        def g(self):
            with self.agg_lock:
                with self._lock:
                    pass
    """))
    both = lockcheck.static_lock_nesting(tmp_path)
    # alias the two modules' locks onto one namespace for the check
    renamed = [lockcheck.StaticNesting(
        n.outer.split(".", 1)[1], n.inner.split(".", 1)[1],
        n.path, n.line) for n in both]
    report = lockcheck.cross_check(renamed, [])
    assert not report["ok"] and report["static_cycles"]


def test_instrumented_lock_nonblocking_and_locked(recording):
    a = lockcheck.make_lock("a._lock")
    assert a.acquire(blocking=False)
    assert a.locked()
    assert not a.acquire(blocking=False)  # failed acquire: no record
    a.release()
    assert not a.locked()
    assert lockcheck.graph()["edges"] == {}


# ========================================================== CLI surface

def test_cli_lint_clean_and_exit_codes(tmp_path, capsys):
    from repro.analysis.__main__ import main
    tree = tmp_path / "pkg"
    tree.mkdir()
    (tree / "ok.py").write_text("x = 1\n")
    assert main(["lint", "--root", str(tree)]) == 0
    (tree / "bad.py").write_text("import time\nx = time.time()\n")
    assert main(["lint", "--root", str(tree)]) == 1
    assert main(["lint", "--root", str(tree), "--write-baseline"]) == 2
    bl = tmp_path / "bl.json"
    assert main(["lint", "--root", str(tree), "--baseline", str(bl),
                 "--write-baseline"]) == 0
    assert main(["lint", "--root", str(tree),
                 "--baseline", str(bl)]) == 0
    capsys.readouterr()


def test_cli_certify_schedule_file(tmp_path, golden_sched, capsys):
    from repro.analysis.__main__ import main
    path = tmp_path / "sched.json"
    path.write_text(golden_sched.to_json())
    assert main(["certify", str(path), "--n-max-rails", str(N_RAILS),
                 "--no-dual"]) == 0
    broken = dataclasses.replace(golden_sched,
                                 e_op=golden_sched.e_op * 2)
    path.write_text(broken.to_json())
    assert main(["certify", str(path), "--no-dual"]) == 1
    out = capsys.readouterr().out
    assert "FAIL" in out and ENERGY_MISMATCH in out


def test_cli_certify_nothing_to_do():
    from repro.analysis.__main__ import main
    assert main(["certify"]) == 2


def test_cli_lockcheck_on_dump(tmp_path, recording, capsys):
    from repro.analysis.__main__ import main
    a = lockcheck.make_lock("a._lock")
    b = lockcheck.make_lock("b._lock")
    with a:
        with b:
            pass
    dump_path = tmp_path / "g.jsonl"
    lockcheck.dump(dump_path)
    src_root = tmp_path / "src"
    src_root.mkdir()
    assert main(["lockcheck", "--dump", str(dump_path),
                 "--root", str(src_root)]) == 0
    # now a conflicting process dump creates a cycle
    lockcheck.reset()
    a2 = lockcheck.make_lock("a._lock")
    b2 = lockcheck.make_lock("b._lock")
    with b2:
        with a2:
            pass
    lockcheck.dump(dump_path)
    assert main(["lockcheck", "--dump", str(dump_path),
                 "--root", str(src_root)]) == 1
    capsys.readouterr()
