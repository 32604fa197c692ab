"""The harness, rehearsed on the CPU through the same loop the window
calls; the main refuses to run anywhere but on a TPU."""

import json
import pathlib
import shutil
import subprocess
import sys

import pytest

from chipbench import system
from chipbench.tests.conftest import run_tiny

ROOT = pathlib.Path(__file__).resolve().parents[2]


@pytest.fixture(autouse=True)
def _program():
    system.import_program()


def test_added_files_are_found_by_name(tiny_bench):
    spec, base = tiny_bench
    out = run_tiny(spec, base, "tiny.tiny-warm")
    assert out["correct"], out["checks"]
    assert out["metrics"]["requests_done"]["value"] == out["attempted"] > 0
    assert set(out["metrics"]) == {"requests_done", "schedules_per_s",
                                   "setup_s"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("mix", ["warm-resolve"])
def test_each_mix_rehearsed(tiny_bench, mix):
    spec, base = tiny_bench
    out = run_tiny(spec, base, f"tiny.{mix}")
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert 0 <= out["checks"]["energy_gap"]["value"] <= \
        out["checks"]["energy_gap"]["limit"]
    assert out["checks"]["float_bits_short"]["value"] == 0


def test_a_suffixed_metric_is_read_by_its_reader(tiny_bench):
    """``<reader>.<suffix>`` without a file of its own names an existing
    reader, so a cell can report a reader's number under its own name."""
    from chipbench import harness

    spec, base = tiny_bench
    assert harness.reader("requests_done.cold", base) is not None
    spec["end_to_end"].append(dict(
        name="requests_done.again", unit="requests", better="higher",
        bound=0.25, source="host_clock", workloads=["tiny.tiny-warm"]))
    out = run_tiny(spec, base, "tiny.tiny-warm")
    assert out["metrics"]["requests_done.again"]["value"] == \
        out["metrics"]["requests_done"]["value"] == out["attempted"]
    with pytest.raises(KeyError):
        harness.reader("no_such_reader.cold", base)


def test_jax_backend_rehearsed(tiny_bench):
    spec, base = tiny_bench
    out = run_tiny(spec, base, "tiny.tiny-warm", backend="jax")
    assert out["correct"], out["checks"]


def test_traced_run_reports_per_layer_metrics(tiny_bench):
    spec, base = tiny_bench
    out = run_tiny(spec, base, "tiny.tiny-warm", traced=True,
                   backend="jax")
    assert out["metrics"]["rounds_per_schedule"]["value"] > 0
    assert out["metrics"]["dispatches_per_schedule"]["value"] >= 0
    assert "device_idle_share" not in out["metrics"]   # no device here


def _main(cwd, *args, env=None):
    cell = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]
    return subprocess.run(
        [sys.executable, "-m", "chipbench", "--workload",
         cell["name"], "--seed", str(2**32 + 3), "--seconds", "1",
         "--trace", "0", *args], cwd=cwd, capture_output=True, text=True,
        env=env, timeout=120)


def test_main_refuses_the_cpu():
    import os

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = _main(ROOT, env=env)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_main_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _main(tmp_path)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_benchmark_json_cells_have_their_files():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    from chipbench import harness

    for cell in spec["workloads"]:
        harness.load_json("configs", cell["config"])
        harness.load_json("traffic", cell["traffic"])
        for traced in (False, True):
            for m in harness.metrics_of(spec, cell["name"], traced):
                harness.reader(m["name"])
