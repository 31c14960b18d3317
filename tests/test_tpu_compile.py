"""The main-path Pallas kernels compile for a TPU v5e at qwen3-0.6b widths.

No chip is needed: the TPU compiler compiles for a described, unattached
``v5e:2x2`` topology.  Each program must hold a Mosaic ``tpu_custom_call``,
so a reference stand-in cannot pass.  The topology is described inside a
fixture (never at import: only one process may load the TPU library), and
all such compiles live in this one file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

# qwen3-0.6b attention: 16 query heads, 8 KV heads, head_dim 128
H, K, HD = 16, 8, 128


@pytest.fixture(scope="module")
def topo():
    """The described topology, with the persistent compile cache off: a
    compile for a described chip is written but cannot be read back."""
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _flash_args(one_chip, seq=2048):
    q = _sds((1, H, seq, HD), jnp.bfloat16, one_chip)
    kv = _sds((1, K, seq, HD), jnp.bfloat16, one_chip)
    return q, kv, kv


def test_flash_forward_compiles(one_chip):
    compiled = ops.flash_attention.lower(
        *_flash_args(one_chip), interpret=False
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_forward_backward_compiles(one_chip):
    def loss(q, k, v):
        out = ops.flash_attention(q, k, v, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    # value_and_grad: the backward recomputes through the reference, so
    # with grad alone XLA would drop the forward kernel as dead code
    grad = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    compiled = grad.lower(*_flash_args(one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_decode_compiles(one_chip):
    rows, page, per_row = 8, 16, 64
    n_pages = rows * per_row + 1
    compiled = ops.paged_attention.lower(
        _sds((rows, H, HD), jnp.bfloat16, one_chip),
        _sds((n_pages, K, page, HD), jnp.bfloat16, one_chip),
        _sds((n_pages, K, page, HD), jnp.bfloat16, one_chip),
        _sds((rows, per_row), jnp.int32, one_chip),
        _sds((rows,), jnp.int32, one_chip),
        interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
