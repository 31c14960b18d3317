"""bench/run.py refuses what it cannot measure, and every file the harness
finds by name is there and says what it is."""

import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness, spec as specmod

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = specmod.load_benchmark(ROOT)
WIDTHS = ("d_model", "n_heads", "d_ff", "vocab", "seq")
#: keys that name a width: a hidden, intermediate, latent, state or
#: projection size, a head size, an expansion factor, experts per token
WIDTH_RE = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|proj|"
                      r"head_size|expan|per_tok|d_model|d_ff")


def _run(cwd, workload="multitask_clip.4task.1chip"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_a_cpu_and_names_it():
    r = _run(ROOT)
    assert r.returncode != 0
    assert "cpu" in r.stderr and "TPU" in r.stderr
    assert "{" not in r.stdout  # no result line


def test_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert "program" in r.stderr
    assert "{" not in r.stdout


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files_state_source_and_cuts(cfg):
    data = specmod.read_json(ROOT / cfg["file"])
    assert data["name"] == cfg["name"] and data["source"] == cfg["source"]
    assert sorted(data["reduced"]) == sorted(cfg["reduced"])
    assert data["precision"]["matmul"]
    # every key cut from the source states its published value beside it
    for key in cfg["reduced"]:
        assert f"published_{key}" in data, key
        assert data[f"published_{key}"] != data[key], key
    assert not set(cfg["reduced"]) & set(WIDTHS)
    assert not [k for k in cfg["reduced"] if WIDTH_RE.search(k)]
    # its architecture's two modules are there
    for part in ("reference", "program"):
        assert (specmod.BENCH / "arch" / data["arch"] / f"{part}.py").is_file()
    assert specmod.load_config(cfg["name"]) == data


@pytest.mark.parametrize("name", ["multitask_clip", "ofasys"])
def test_published_configs_cut_depth_by_three(name):
    data = specmod.load_config(name)
    assert data["assumed"] and data["precision"]["params"] == "float32"
    assert data["reduced"] == ["layers"]
    # the cut is in depth alone, by one factor for every component
    ratios = {data["published_layers"][k] / v for k, v in data["layers"].items()}
    assert ratios == {3.0}


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_files_load_by_name(cell):
    spec = specmod.load_spec(cell["config"], cell["traffic"])
    assert len(spec["flows"]) == specmod.load_traffic(cell["traffic"])["tasks"]
    limits = specmod.read_json(specmod.BENCH / "limits" / f"{cell['name']}.json")
    assert set(limits) >= {"loss_gap", "grad_gap", "update_gap"}
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    names = {m["name"] for m in harness.metrics_for(BENCH, cell["name"], False)}
    assert {"step_s", "setup_s"} <= names
    assert harness.metrics_for(BENCH, cell["name"], True)


@pytest.mark.parametrize(
    "metric", BENCH["end_to_end"] + BENCH["per_layer"], ids=lambda m: m["name"])
def test_every_metric_has_a_reader(metric):
    read = harness.load_reader(metric["name"])
    assert read({}) is None  # nothing to read: left out, never 0


def test_peaks_for_the_chip():
    assert harness.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(SystemExit):
        harness.peak_flops("TPU v9")


def test_benchmark_json_is_what_the_harness_reads():
    text = (ROOT / "BENCHMARK.json").read_text()
    assert json.loads(text) == BENCH
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
