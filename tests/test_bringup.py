"""No silent device fallbacks, the compile-cache helper, and the chip smoke
test's refusal to run without a TPU."""

import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

from repro.core import ClusterSpec, plan
from repro.launch import compile_cache
from repro.parallel import mesh_over_devices
from repro.runtime import WaveEngine, tiny_multitask_clip

REPO = pathlib.Path(__file__).resolve().parent.parent


def test_mesh_over_devices_raises_on_missing_ids():
    n = jax.device_count()
    assert mesh_over_devices(range(n)).devices.size == n
    with pytest.raises(ValueError, match="do not exist"):
        mesh_over_devices(range(n + 1))
    with pytest.raises(ValueError, match="empty"):
        mesh_over_devices([])


def test_distributed_engine_raises_on_plan_for_missing_devices():
    n = jax.device_count()
    model, _ = tiny_multitask_clip(n_tasks=3)
    fits = plan(model.graph, ClusterSpec(n_devices=n, island_size=n,
                                         mem_bytes=1e13))
    too_big = plan(model.graph, ClusterSpec(n_devices=n + 2, island_size=n + 2,
                                            mem_bytes=1e13))
    assert max(d for s in too_big.steps for d in s.devices) >= n
    with pytest.raises(ValueError, match="plan for the devices that exist"):
        WaveEngine(model, too_big, distributed=True)
    eng = WaveEngine(model, fits, distributed=True)
    with pytest.raises(ValueError, match="plan for the devices that exist"):
        eng.rebind(too_big)
    assert eng.plan is fits  # a refused rebind leaves the engine as it was
    # the single-process engine treats plan device ids as labels
    WaveEngine(model, too_big, distributed=False)


def test_compile_cache_follows_env_else_checkout(monkeypatch, tmp_path):
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev  # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path  # fixed path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def _run(args, cwd):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "PYTHONPATH")}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=240)


def test_importing_the_package_sets_no_compile_cache():
    out = _run(["-c", (
        "import sys; sys.path.insert(0, 'src'); import jax; "
        "import repro.launch.train, repro.launch.serve, repro.launch.fleet; "
        "print(jax.config.jax_compilation_cache_dir)")], REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "None"


def test_chip_smoke_refuses_to_run_without_a_tpu(tmp_path):
    out = _run(["chip_smoke.py"], REPO)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout
    # alone in a directory, without the program, it fails too
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    out = _run(["chip_smoke.py"], tmp_path)
    assert out.returncode != 0 and '"ok"' not in out.stdout
