"""The configurations load, name their source, and their networks are
the program's: every layer, and its characterization, agrees with the
reference's."""

import dataclasses

import pytest

from chipbench import generator, harness, system
from chipbench.reference import networks, physics

SPEC = harness.load_spec()


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda e: e["name"])
def test_config_file_loads_and_names_its_source(entry):
    cfg = harness.load_json("configs", entry["name"])
    assert cfg["name"] == entry["name"]
    assert cfg["source"] == entry["source"]
    assert entry["file"] == f"chipbench/configs/{entry['name']}.json"
    assert set(cfg["reduced"]) == set(entry["reduced"])


CONFIGS = sorted(p.stem for p in (harness.HERE / "configs").glob("*.json"))
MIXES = sorted(p.stem for p in (harness.HERE / "traffic").glob("*.json"))


@pytest.mark.parametrize("name", CONFIGS)
def test_every_config_file_states_its_source_and_limits(name):
    cfg = harness.load_json("configs", name)
    assert cfg["name"] == name and cfg["source"].startswith("https://")
    assert set(cfg["limits"]) == {"energy_gap", "violations",
                                  "ledger_rel_err", "float_bits_short"}
    assert all(v is not None for v in cfg["limits"].values())
    assert cfg["guarantees"]["max_rails"] == cfg["n_max_rails"]


@pytest.mark.parametrize("config", CONFIGS)
@pytest.mark.parametrize("traffic", MIXES)
def test_every_network_characterizes_as_the_program_does(config, traffic):
    system.import_program()
    from repro.hw.edge40nm import Edge40nmAccelerator
    from repro.models.edge_cnn import edge_network
    from repro.perfmodel import LayerSpec, characterize_network

    cfg = harness.load_json("configs", config)
    mix = harness.load_json("traffic", traffic)
    acc = Edge40nmAccelerator(**cfg["accelerator"])
    ref_acc = generator.accelerator(cfg)
    assert ref_acc.levels() == acc.levels()
    for name, hw in generator.variants(cfg, mix):
        layers = networks.network(name, hw)
        specs = [LayerSpec(**dataclasses.asdict(x)) for x in layers]
        assert specs == edge_network(name, hw)
        costs = characterize_network(specs, acc)
        assert [(c.cycles, c.dyn_energy_nom) for c in costs] == \
            [physics.characterize(x, ref_acc) for x in layers]


def test_reference_ledger_matches_the_program_certifier():
    """The reference's re-derivation of a compiled schedule agrees with
    the program's own certifier to rounding."""
    system.import_program()
    from repro.analysis.certify import certify

    cfg = harness.load_json("configs", "edge40nm-5rail")
    mix = harness.load_json("traffic", "warm-resolve")
    compiler = system.Compiler(cfg, "numpy")
    req = generator.Traffic(cfg, mix, 8).take(1)[0]
    sched = compiler.compile(req)
    ours = physics.ledger(req.layers(), generator.accelerator(cfg),
                          sched.layer_voltages, sched.t_max)
    theirs = certify(sched, compiler.program_request(req).specs,
                     n_max_rails=cfg["n_max_rails"], dual=False).derived
    for key in ("t_infer", "e_op", "e_trans", "e_total"):
        assert ours[key] == pytest.approx(theirs[key], rel=1e-13)
    assert ours["n_rail_switches"] == theirs["n_rail_switches"]
