"""v5e compiles of the device code on the main path, without a chip.

The TPU compiler is installed with jaxlib, and it compiles for a chip that
is described, not attached: these tests catch what interpret mode cannot,
such as block shapes the TPU's tiling refuses or kernels that overflow
VMEM.  Nothing runs, so they say nothing about results or speed.

Shapes are mamba2-780m's published widths (48 SSD heads x 64, state 128,
chunk 256): the serving prefill (1 x 512 tokens) and the training step
(4 x 2048 tokens, forward and the reference-VJP backward).

The topology is described in a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and under several
test workers the others must still collect the same tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.mamba2_780m import CONFIG


@pytest.fixture(scope="module")
def topo():
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    mp.undo()


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_persistent_cache):
    return SingleDeviceSharding(topo.devices[0])


def _ssd_args(one_chip, batch: int, seq: int, x_dtype):
    cfg = CONFIG
    l, h, p, n = (cfg.ssm_chunk, cfg.ssm_heads, cfg.ssm_head_dim,
                  cfg.ssm_state)
    nc = seq // l

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return (spec((batch, nc, l, h, p), x_dtype),
            spec((batch, nc, l, h), jnp.float32),
            spec((batch, nc, l, h), jnp.float32),
            spec((batch, nc, l, n), jnp.float32),
            spec((batch, nc, l, n), jnp.float32))


@pytest.mark.parametrize("batch,seq", [(1, 512), (4, 2048)],
                         ids=["prefill", "train"])
def test_ssd_kernel_compiles_for_v5e(one_chip, batch, seq):
    from repro.kernels.ssd.ops import ssd_intra_chunk
    args = _ssd_args(one_chip, batch, seq, jnp.bfloat16)
    compiled = ssd_intra_chunk.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_kernel_grad_compiles_for_v5e(one_chip):
    from repro.kernels.ssd.ops import ssd_intra_chunk

    def loss(*a):
        y, st = ssd_intra_chunk(*a)
        return y.sum() + st.sum()

    args = _ssd_args(one_chip, 4, 2048, jnp.bfloat16)
    grad = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))
    compiled = grad.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
