"""A throwaway benchmark, written as files only: a one-network
configuration, the real traffic mixes, a metric reader of its own, and
the cells that join them."""

import json
import shutil
import time

import pytest

from chipbench import harness

TINY_CONFIG = {
    "name": "tiny-edge", "source": "https://arxiv.org/abs/2603.23882",
    "accelerator": {}, "policy": "pfdnn", "n_max_rails": 2,
    "precision": "float64", "networks": {"squeezenet1.1": 224},
    "guarantees": {"max_rails": 2, "deadline_slop_s": 1e-15},
    "limits": {"energy_gap": 0.005, "violations": 0, "ledger_rel_err": 1e-12,
               "float_bits_short": 0},
    "reference_sample": 4, "reduced": ["networks", "n_max_rails"],
}

READER = '''
def read(run):
    return float(len(run.window.records))
'''


@pytest.fixture
def tiny_bench(tmp_path):
    """(spec, base directory) of a benchmark that adds a configuration,
    a mix and a reader as new files beside copies of the real ones."""
    for kind in ("configs", "traffic", "metrics"):
        shutil.copytree(harness.HERE / kind, tmp_path / kind)
    (tmp_path / "configs" / "tiny-edge.json").write_text(
        json.dumps(TINY_CONFIG))
    mix = harness.load_json("traffic", "warm-resolve")
    mix.update(strata=2, warmup_all_subsets=[], warmup_rate_frac=[0.5])
    (tmp_path / "traffic" / "tiny-warm.json").write_text(json.dumps(mix))
    (tmp_path / "metrics" / "requests_done.py").write_text(READER)
    spec = harness.load_spec()
    for traffic in ("tiny-warm", "warm-resolve"):
        spec["workloads"].append({
            "name": f"tiny.{traffic}", "config": "tiny-edge",
            "traffic": traffic, "chips": 1, "why": "test"})
    tiny = [w["name"] for w in spec["workloads"]
            if w["name"].startswith("tiny.")]

    def metric(name, unit, better, **extra):
        return dict(name=name, unit=unit, better=better, **extra)

    spec["end_to_end"] = [
        metric("schedules_per_s", "schedules/s", "higher", bound=0.25,
               source="host_clock", workloads=tiny),
        metric("requests_done", "requests", "higher", bound=0.25,
               source="host_clock", workloads=["tiny.tiny-warm"]),
        metric("setup_s", "s", "lower", bound=0.25, source="host_clock")]
    spec["per_layer"] = [
        metric(name, unit, "lower", source=source, layer=layer,
               moves="schedules_per_s", workloads=["tiny.tiny-warm"])
        for name, unit, source, layer in (
            ("rounds_per_schedule", "rounds", "program_counter", "sweep"),
            ("dispatches_per_schedule", "dispatches", "program_counter",
             "backend"),
            ("device_idle_share", "%", "device_trace", "device"))]
    return spec, tmp_path


def run_tiny(spec, base, cell, *, seconds=1.0, traced=False,
             backend="numpy", seed=2**31 + 17):
    return harness.run_cell(spec, cell, seed, seconds, traced,
                            t_start=time.perf_counter(), backend=backend,
                            base=base, log=lambda line: None)
