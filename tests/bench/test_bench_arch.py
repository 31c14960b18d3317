"""Architectures as files: the benchmark finds a configuration's layer code
by its ``"arch"``, the yardstick of the published configurations stays where
it was, and a new architecture needs nothing but new files."""

import ast
import hashlib
import json
import math
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench import flops, reference, spec as specmod
from conftest import tiny_spec

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEED = 2**33 + 12345

#: the yardstick of each published configuration under its first cell's
#: traffic, fixed when its layer code moved under ``bench/arch/mt_dense``:
#: FLOPs per step and the parameter layout at the published widths; at the
#: tiny size and ``SEED``, the sha256 of the weights and batches and the
#: reference loss on batch 0 at ``highest`` precision
PINNED = {
    "multitask_clip": {
        "traffic": "4task_b4", "step_flops": 2403702177792.0,
        "params": 227574788, "leaves": 186,
        "sha256": "e841a8dd4572d829b29d09aa9a2907194267f35a3746281c99d8c80b3aaadd71",
        "loss": 1.9425113201141357},
    "ofasys": {
        "traffic": "4task_b2", "step_flops": 2595333095424.0,
        "params": 401253888, "leaves": 189,
        "sha256": "3115c3ef1f6ac2f8cc53bb982ef655685ff940de764fc01abae6a6984c403f29",
        "loss": 5.090641498565674},
}


def inputs_digest(spec, seed) -> str:
    """sha256 over the bytes of every weight and batch leaf, in tree order."""
    h = hashlib.sha256()
    for x in jax.tree.leaves((specmod.make_weights(spec, seed),
                              specmod.make_batches(spec, seed))):
        h.update(np.asarray(x).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", PINNED)
def test_step_flops_pinned(name):
    spec = specmod.load_spec(name, PINNED[name]["traffic"])
    assert flops.step_flops(spec) == PINNED[name]["step_flops"]


@pytest.mark.parametrize("name", PINNED)
def test_param_layout_pinned(name):
    spec = specmod.load_spec(name, PINNED[name]["traffic"])
    leaves = jax.tree.leaves(specmod.arch(spec).param_shapes(spec),
                             is_leaf=specmod._is_leaf)
    assert len(leaves) == PINNED[name]["leaves"]
    assert sum(math.prod(shape) for shape, _ in leaves) == PINNED[name]["params"]


@pytest.mark.parametrize("name", PINNED)
def test_tiny_inputs_pinned(name):
    spec = tiny_spec(name, PINNED[name]["traffic"])
    assert inputs_digest(spec, SEED) == PINNED[name]["sha256"]


@pytest.mark.parametrize("name", PINNED)
def test_reference_loss_pinned(name):
    spec = tiny_spec(name, PINNED[name]["traffic"])
    weights = specmod.make_weights(spec, SEED)
    batch = specmod.make_batches(spec, SEED)[0]
    with jax.default_matmul_precision("highest"):
        got = float(specmod.arch(spec).loss(spec, weights, batch, reference.dot))
    assert got == pytest.approx(PINNED[name]["loss"], rel=1e-6)


# --------------------------------------------------------------- imports
def _imports_repro(path: pathlib.Path) -> bool:
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            names = [node.module or ""]
        else:
            continue
        if any(n == "repro" or n.startswith("repro.") for n in names):
            return True
    return False


def test_only_the_program_modules_import_repro():
    bench = ROOT / "bench"
    allowed = {bench / "program.py"} | set(bench.glob("arch/*/program.py"))
    importers = {p for p in bench.rglob("*.py") if _imports_repro(p)}
    assert importers <= allowed, sorted(map(str, importers - allowed))
    assert bench / "program.py" in importers


# ----------------------------------------------------------------- probe
PROBE_CELL = "probe.4task.1chip"
#: a loss 1% off the reference's, as a probe architecture that the harness
#: must be seen to use
SCALED_LOSS = '''

_loss = loss


def loss(spec, p, batch, mm):
    return 1.01 * _loss(spec, p, batch, mm)
'''

_RUN_PROBE = """
import json, pathlib, sys
sys.path[:0] = [{tests!r}, {root!r}, {src!r}]
from conftest import tiny_spec
from bench import harness, spec as specmod
bench = specmod.load_benchmark()
cell = specmod.find_cell(bench, {cell!r})
spec = tiny_spec(cell["config"], cell["traffic"])
r = harness.run_cell(bench, cell, {seed}, 0.01, False, t_start=0.0,
                     compiles=harness.CompileCounter(),
                     out_dir=pathlib.Path({root!r}) / ".bench_out",
                     log=lambda s: None, spec=spec)
print(json.dumps({{"correct": r["correct"], "checks": r["checks"],
                   "arch": [specmod.arch(spec, p).__file__
                            for p in ("reference", "program")],
                   "harness": harness.__file__}}))
"""


def _files(root: pathlib.Path):
    return {p.relative_to(root) for p in root.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def _add_probe(copy: pathlib.Path, scaled: bool):
    """Only new files: an architecture ``probe`` (``mt_dense`` under another
    name), a configuration naming it, a traffic file, a limits file, and
    one ``configs`` and one ``workloads`` entry."""
    bench = copy / "bench"
    shutil.copytree(bench / "arch" / "mt_dense", bench / "arch" / "probe",
                    ignore=shutil.ignore_patterns("__pycache__"))
    if scaled:
        with open(bench / "arch" / "probe" / "reference.py", "a") as f:
            f.write(SCALED_LOSS)
    cfg = specmod.load_config("ofasys")
    cfg.update(name="probe", arch="probe")
    (bench / "configs" / "probe.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "probe_b2.json").write_text(json.dumps(
        {"tasks": 4, "batch_per_task": 2, "distinct_batches": 4}))
    shutil.copy(bench / "limits" / "ofasys.4task.1chip.json",
                bench / "limits" / f"{PROBE_CELL}.json")
    doc = json.loads((copy / "BENCHMARK.json").read_text())
    doc["configs"].append({
        "name": "probe", "source": cfg["source"], "file": "bench/configs/probe.json",
        "reduced": ["layers"], "why": "an architecture that arrives as files"})
    doc["workloads"].append({
        "name": PROBE_CELL, "config": "probe", "traffic": "probe_b2",
        "chips": 1, "why": "the probe architecture through the harness"})
    (copy / "BENCHMARK.json").write_text(json.dumps(doc, indent=1))


@pytest.mark.parametrize("scaled", [False, True], ids=["sound", "scaled_loss"])
def test_an_architecture_arrives_as_new_files(scaled, tmp_path):
    repo_bench = specmod.load_benchmark(ROOT)
    copy = (tmp_path / "checkout").resolve()
    copy.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", copy)
    for p in repo_bench["paths"]:
        shutil.copytree(ROOT / p, copy / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    _add_probe(copy, scaled)

    # every file the copy shares with the repo is as the repo has it, but
    # BENCHMARK.json, which gains the two entries alone
    for rel in _files(copy):
        if rel == pathlib.Path("BENCHMARK.json"):
            doc = json.loads((copy / rel).read_text())
            assert doc["configs"].pop()["name"] == "probe"
            assert doc["workloads"].pop()["name"] == PROBE_CELL
            assert doc == repo_bench
        elif (ROOT / rel).is_file():
            assert (copy / rel).read_bytes() == (ROOT / rel).read_bytes(), rel
        else:
            assert rel.parts[:3] == ("bench", "arch", "probe") or rel in {
                pathlib.Path("bench/configs/probe.json"),
                pathlib.Path("bench/traffic/probe_b2.json"),
                pathlib.Path(f"bench/limits/{PROBE_CELL}.json")}, rel

    code = _RUN_PROBE.format(tests=str(copy / "tests" / "bench"),
                             root=str(copy), src=str(ROOT / "src"),
                             cell=PROBE_CELL, seed=SEED)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, "-c", code], cwd=copy, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    probe = copy / "bench" / "arch" / "probe"
    assert r["arch"] == [str(probe / "reference.py"), str(probe / "program.py")]
    assert r["harness"] == str(copy / "bench" / "harness.py")
    assert r["correct"] is not scaled, r["checks"]
    if scaled:  # the probe's reference, 1% off, is what failed
        assert r["checks"]["loss_gap"]["value"] == pytest.approx(0.01, rel=0.05)
