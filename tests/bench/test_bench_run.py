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
#: (the catalog's depth, ``num_hidden_layers``, is no width)
WIDTH_RE = re.compile(r"(_dim|_rank)$|hidden(?!_layers)|intermediate|latent|"
                      r"state|proj|head_size|expan|per_tok|d_model|d_ff")


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


def check_cuts(data, reduced):
    """The cuts a configuration file states: every key cut from the source
    states its published value beside it, and no width is cut.

    A configuration that holds a chip's share of a deployment states it as
    ``share``: ``chips``, how many chips share each layer; ``holds``, what
    each holds, in words; ``experts`` and ``vocab``, the keys that count the
    routed experts and the vocabulary rows held here. Those keys, and no
    other count, may be listed in ``reduced``: held x chips is the published
    count, exactly for experts and by ceiling division for a vocabulary.
    Experts per token and every width stay refused."""
    assert sorted(data["reduced"]) == sorted(reduced)
    for key in reduced:
        assert f"published_{key}" in data, key
        assert data[f"published_{key}"] != data[key], key
    share = data.get("share", {})
    experts, vocab = share.get("experts", []), share.get("vocab", [])
    if share:
        assert isinstance(share["chips"], int) and share["chips"] > 1
        assert share["holds"] and (experts or vocab)
    for key in experts + vocab:
        assert key in reduced and not WIDTH_RE.search(key), key
    for key in experts:
        assert "expert" in key, key
        assert data[key] * share["chips"] == data[f"published_{key}"], key
    for key in vocab:
        assert "vocab" in key, key
        assert data[key] == -(-data[f"published_{key}"] // share["chips"]), key
    cuts = [k for k in reduced if k not in experts + vocab]
    assert not set(cuts) & set(WIDTHS)
    assert not [k for k in cuts if WIDTH_RE.search(k)]


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files_state_source_and_cuts(cfg):
    data = specmod.read_json(ROOT / cfg["file"])
    assert data["name"] == cfg["name"] and data["source"] == cfg["source"]
    assert data["precision"]["matmul"]
    check_cuts(data, cfg["reduced"])
    # its architecture's two modules are there
    for part in ("reference", "program"):
        assert (specmod.BENCH / "arch" / data["arch"] / f"{part}.py").is_file()
    assert specmod.load_config(cfg["name"]) == data


#: a configuration at a chip's share, in the catalog's keys: a mixture of
#: experts language model whose every layer is shared by 8 chips, each
#: holding 8 of its 64 routed experts and 20480 of its 163840 words
SHARED = {
    "num_hidden_layers": 5, "published_num_hidden_layers": 27,
    "hidden_size": 2048, "moe_intermediate_size": 1408,
    "num_experts_per_tok": 6, "kv_lora_rank": 512,
    "n_routed_experts": 8, "published_n_routed_experts": 64,
    "vocab_size": 20480, "published_vocab_size": 163840,
    "share": {"chips": 8, "holds": "8 of each expert layer's 64 routed "
              "experts and one eighth of the vocabulary's rows",
              "experts": ["n_routed_experts"], "vocab": ["vocab_size"]},
    "reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"],
}


def _shared(**change):
    data = json.loads(json.dumps(SHARED))
    for key, value in change.items():
        if isinstance(value, dict):
            data[key].update(value)
        else:
            data[key] = value
    return data


def test_a_chips_share_may_cut_experts_and_vocabulary():
    check_cuts(SHARED, SHARED["reduced"])
    # the vocabulary's slice rounds up
    check_cuts(_shared(vocab_size=7433, published_vocab_size=59457),
               SHARED["reduced"])


@pytest.mark.parametrize("bad", [
    # a width cut, beside its published value
    _shared(hidden_size=1024, published_hidden_size=2048,
            reduced=SHARED["reduced"] + ["hidden_size"]),
    _shared(kv_lora_rank=256, published_kv_lora_rank=512,
            reduced=SHARED["reduced"] + ["kv_lora_rank"]),
    # an expert's width or the experts per token named as a share
    _shared(moe_intermediate_size=176, published_moe_intermediate_size=1408,
            reduced=SHARED["reduced"] + ["moe_intermediate_size"],
            share={"experts": ["n_routed_experts", "moe_intermediate_size"]}),
    _shared(num_experts_per_tok=1, published_num_experts_per_tok=6,
            reduced=SHARED["reduced"] + ["num_experts_per_tok"],
            share={"experts": ["n_routed_experts", "num_experts_per_tok"]}),
    # shares that do not multiply back to the published counts
    _shared(n_routed_experts=16),
    _shared(vocab_size=20000),
    _shared(share={"chips": 4}),
    # the vocabulary cut with no share stated
    {k: v for k, v in _shared(reduced=["vocab"], vocab=20480,
                              published_vocab=163840).items() if k != "share"},
    # a share that lists a key left out of ``reduced``
    _shared(reduced=["num_hidden_layers", "vocab_size"]),
], ids=["hidden", "latent_rank", "expert_width", "experts_per_token",
        "experts_times_chips", "vocab_times_chips", "chips", "vocab_no_share",
        "not_reduced"])
def test_a_chips_share_cuts_no_width(bad):
    with pytest.raises(AssertionError):
        check_cuts(bad, bad["reduced"])


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
