"""Shared helpers for the benchmark's CPU tests: the checkout on the path,
and tiny versions of the benchmark's configurations (same structure, small
widths) for runs a test can hold."""

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def tiny_spec(config: str, traffic: str, batch: int = 4):
    """The configuration's components, tasks and sharing at width 32, 2
    layers, 8 tokens and a 97-word vocabulary (one width and length keep the
    eager engine's compilations few)."""
    from bench import spec

    cfg = spec.load_config(config)
    for name, c in cfg["components"].items():
        c.update(d_model=32, n_heads=4, d_ff=64, seq=8)
        if c["kind"] == "decoder":
            c.update(vocab=97)
        if name in cfg["layers"]:
            cfg["layers"][name] = 2
    tr = dict(spec.load_traffic(traffic), batch_per_task=batch)
    return spec.build_spec(cfg, tr)


@pytest.fixture(params=[("multitask_clip", "4task_b4"), ("ofasys", "4task_b2")],
                ids=["multitask_clip", "ofasys"])
def tiny(request):
    return tiny_spec(*request.param)
