"""A cell's model and inputs as plain data, and the seeded weights and batches.

Nothing here imports the program. ``load_spec`` turns a configuration file
(``bench/configs/<config>.json``) and a traffic file
(``bench/traffic/<traffic>.json``) into one plain dict that the harness, the
program adapter and the reference all read. ``arch`` finds the code that
belongs to the configuration's architecture (its ``"arch"`` key) under
``bench/arch/<arch>/``. ``make_weights`` and ``make_batches`` build the
parameters and the input batches from a seed, each in one jitted call on the
default device; the same seed gives the same arrays in any process (no
``hash()``, no process state).
"""

from __future__ import annotations

import functools
import importlib.util
import json
import math
import pathlib
import sys
from types import ModuleType
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def read_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: pathlib.Path = ROOT) -> Dict[str, Any]:
    return read_json(root / "BENCHMARK.json")


def find_cell(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json; have "
                     f"{[c['name'] for c in bench['workloads']]}")


def load_config(name: str) -> Dict[str, Any]:
    return read_json(BENCH / "configs" / f"{name}.json")


def load_traffic(name: str) -> Dict[str, Any]:
    return read_json(BENCH / "traffic" / f"{name}.json")


def load_file(path: pathlib.Path, name: str) -> ModuleType:
    """The Python file at ``path`` as a module registered as ``name``."""
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    sys.modules[name] = mod
    mod_spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def _arch_module(arch_name: str, part: str) -> ModuleType:
    path = BENCH / "arch" / arch_name / f"{part}.py"
    if not path.is_file():
        raise SystemExit(f"no {part}.py for architecture {arch_name!r} "
                         f"under bench/arch")
    return load_file(path, f"bench_arch_{arch_name}_{part}")


def arch(spec: Dict[str, Any], part: str = "reference") -> ModuleType:
    """The module ``bench/arch/<arch>/<part>.py`` of a spec's (or a
    configuration's) architecture, loaded once per process.

    ``reference`` is plain ``jax.numpy`` and imports nothing of the program:
    ``param_shapes(spec)``, ``loss(spec, params, batch, mm)``,
    ``forward_flops(spec)`` and ``tiny(cfg)``. ``program`` is the adapter,
    ``build_model(spec, weights)``, and only ``bench/program.py`` asks for
    it."""
    return _arch_module(spec["arch"], part)


def build_spec(cfg: Dict[str, Any], traffic: Dict[str, Any]) -> Dict[str, Any]:
    """Components with their run depth, and the traffic's flows. Every key of
    a component but its ``source`` is kept, so that an architecture reads
    its own."""
    comps = {}
    for name, c in cfg["components"].items():
        comp = {k: v for k, v in c.items() if k != "source"}
        comp.update(
            n_layers=cfg["layers"].get(name, 1),
            n_heads=c.get("n_heads", 1),
            d_ff=c.get("d_ff", 4 * c["d_model"]),
            vocab=c.get("vocab", 0),
            seq=c.get("seq", 1),
            shared=bool(c.get("shared", False)),
            merge_shared=bool(c.get("merge_shared", False)),
        )
        comps[name] = comp
    tasks = cfg["tasks"][: traffic["tasks"]]
    used = {n for t in tasks for br in t["branches"] for n in br} | {
        n for t in tasks for n in t["join"]}
    comps = {n: c for n, c in comps.items() if n in used}
    flows = []
    for t in tasks:
        names = [n for br in t["branches"] for n in br] + list(t["join"])
        flows.append({
            "task": t["task"],
            "branches": [list(br) for br in t["branches"]],
            "join": list(t["join"]),
            "batch": int(traffic["batch_per_task"]),
            "seq": {n: comps[n]["seq"] for n in names
                    if comps[n]["kind"] != "contrastive"},
        })
    return {
        "name": cfg["name"],
        "arch": cfg["arch"],
        "components": comps,
        "flows": flows,
        "optimizer": dict(cfg["optimizer"]),
        "precision": cfg["precision"]["matmul"],
        "distinct_batches": int(traffic["distinct_batches"]),
    }


def load_spec(config: str, traffic: str) -> Dict[str, Any]:
    return build_spec(load_config(config), load_traffic(traffic))


# ------------------------------------------------------------------ layout
def instance_of(spec: Dict[str, Any], task: str, comp: str) -> str:
    """Parameter instance a task's use of a component runs on: one shared
    instance, or one per task."""
    c = spec["components"][comp]
    return comp if (c["shared"] or c["merge_shared"]) else f"{task}:{comp}"


def instances(spec: Dict[str, Any]) -> List[Tuple[str, str]]:
    """Sorted ``(instance, component)`` pairs the flows activate."""
    out = set()
    for f in spec["flows"]:
        for comp in [n for br in f["branches"] for n in br] + f["join"]:
            out.add((instance_of(spec, f["task"], comp), comp))
    return sorted(out)


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], tuple)


def seed_keys(seed: int) -> Dict[str, jax.Array]:
    """Independent keys for weights and data from any whole-number seed."""
    words = np.random.SeedSequence(int(seed)).generate_state(4, np.uint32)
    return {
        "weights": jax.random.wrap_key_data(words[:2]),
        "data": jax.random.wrap_key_data(words[2:]),
    }


def frozen(spec: Dict[str, Any]) -> str:
    """A spec as a hashable key, for the memo of its jitted builders."""
    return json.dumps(spec, sort_keys=True)


def make_weights(spec: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """All float32 parameters from the seed, in one jitted call: one normal
    draw cut into the leaves of the architecture's ``param_shapes``, in
    their order, each scaled by its rule. A leaf is ``(shape, init)``, with
    ``init`` ``("normal", scale)`` or ``("const", value)``."""
    return _weights_fn(frozen(spec))(seed_keys(seed)["weights"])


@functools.lru_cache(maxsize=4)
def _weights_fn(key: str):
    spec = json.loads(key)
    leaves, treedef = jax.tree.flatten(arch(spec).param_shapes(spec),
                                       is_leaf=_is_leaf)
    sizes = [math.prod(shape) if rule == "normal" else 0
             for shape, (rule, _) in leaves]
    starts = np.cumsum([0] + sizes)

    def build(key):
        flat = jax.random.normal(key, (int(starts[-1]),), jnp.float32)
        out = []
        for (shape, (rule, v)), a in zip(leaves, starts):
            if rule == "normal":
                out.append(flat[a:a + math.prod(shape)].reshape(shape) * v)
            else:
                out.append(jnp.full(shape, v, jnp.float32))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(build)


def make_batches(spec: Dict[str, Any], seed: int) -> List[Dict[str, Dict]]:
    """``distinct_batches`` batches (task -> inputs), every row different,
    in one jitted call: one normal draw for the tower inputs and one draw
    of tokens, cut in order."""
    return _batches_fn(frozen(spec))(seed_keys(seed)["data"])


@functools.lru_cache(maxsize=4)
def _batches_fn(key: str):
    spec = json.loads(key)
    comps = spec["components"]
    emb, tok = [], []  # (batch index, task, input name, shape)
    for j in range(spec["distinct_batches"]):
        for f in spec["flows"]:
            for br in f["branches"]:
                c = comps[br[0]]
                if c["kind"] == "tower":
                    emb.append((j, f["task"], br[0],
                                (f["batch"], f["seq"][br[0]], c["d_model"])))
            for comp in f["join"]:
                c = comps[comp]
                if c["kind"] == "decoder":
                    tok.append((j, f["task"], c["vocab"],
                                (f["batch"], f["seq"][comp] + 1)))
    if len({v for *_, v, _ in tok}) > 1:
        raise ValueError("decoders of one spec must share a vocabulary")

    def build(key):
        k_emb, k_tok = jax.random.split(key)
        pool = [{f["task"]: {} for f in spec["flows"]}
                for _ in range(spec["distinct_batches"])]
        flat = jax.random.normal(
            k_emb, (sum(math.prod(x[3]) for x in emb),), jnp.float32)
        a = 0
        for j, task, name, shape in emb:
            pool[j][task][name] = flat[a:a + math.prod(shape)].reshape(shape)
            a += math.prod(shape)
        if tok:
            ids = jax.random.randint(
                k_tok, (sum(math.prod(x[3]) for x in tok),), 0, tok[0][2],
                jnp.int32)
            a = 0
            for j, task, _, shape in tok:
                t = ids[a:a + math.prod(shape)].reshape(shape)
                pool[j][task]["tokens"], pool[j][task]["labels"] = t[:, :-1], t[:, 1:]
                a += math.prod(shape)
        return pool

    return jax.jit(build)
