"""Shared helpers for the benchmark's CPU tests: the checkout on the path,
and tiny versions of the benchmark's configurations (same structure, small
widths, each shrunk by its architecture's ``tiny``) for runs a test can
hold."""

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

_BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
#: ``(config, traffic)`` for each configuration of BENCHMARK.json, with the
#: traffic of its first cell
CONFIG_CELLS = [
    (cfg["name"], next(c["traffic"] for c in _BENCH["workloads"]
                       if c["config"] == cfg["name"]))
    for cfg in _BENCH["configs"]
]
CONFIG_IDS = [name for name, _ in CONFIG_CELLS]


def tiny_spec(config: str, traffic: str, batch: int = 4):
    """The configuration shrunk by its architecture's ``tiny``, under its
    traffic with ``batch`` samples per task."""
    from bench import spec

    cfg = spec.load_config(config)
    tr = dict(spec.load_traffic(traffic), batch_per_task=batch)
    return spec.build_spec(spec.arch(cfg).tiny(cfg), tr)


@pytest.fixture(params=CONFIG_CELLS, ids=CONFIG_IDS)
def tiny(request):
    return tiny_spec(*request.param)
