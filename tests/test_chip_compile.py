"""The main path's Pallas kernel, compiled for the chip with no chip.

The TPU's compiler is installed here and compiles for a device that is
described and not attached (``jax.experimental.topologies``).  Every
other test of ``flash_attention`` runs it in interpret mode on the CPU;
these hand the same kernel, ``interpret=False``, to the v5e's compiler
at the widths the main path uses, so a kernel the chip would refuse
(tiling, VMEM) fails here at no chip time.  A compile that passes is a
compile, not a run: ``chip_smoke.py`` is the run.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from mxnet_tpu.op.pallas.flash_attention import flash_attention

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # or it logs under /tmp


@pytest.fixture(scope="module")
def v5e():
    """One device of a described v5e 2x2, persistent cache off: a
    compile for a described device is written to the cache and cannot
    be read back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                          # noqa: BLE001
        pytest.skip("cannot describe a v5e:2x2 here: %s" % e)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


SHAPES = [
    pytest.param((8, 1024, 16, 64), jnp.bfloat16, True, id="gpt2-medium"),
    pytest.param((2, 8192, 8, 64), jnp.bfloat16, True, id="long-context"),
    pytest.param((4, 2048, 8, 128), jnp.bfloat16, True, id="head-dim-128"),
    pytest.param((4, 1000, 8, 64), jnp.float32, False, id="ragged-f32"),
    pytest.param((1, 4096, 20, 256), jnp.bfloat16, True, id="glm-4.7-flash"),
]


def _compiled_text(fn, shape, dtype, sharding):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return jax.jit(fn).lower(x, x, x).compile().as_text()


@pytest.mark.parametrize("shape,dtype,causal", SHAPES)
def test_flash_forward_compiles_for_v5e(v5e, shape, dtype, causal):
    def forward(q, k, v):
        return flash_attention(q, k, v, causal=causal, interpret=False)

    assert "tpu_custom_call" in _compiled_text(forward, shape, dtype, v5e)


@pytest.mark.parametrize("shape,dtype,causal", SHAPES)
def test_flash_gradient_compiles_for_v5e(v5e, shape, dtype, causal):
    """The reverse mode is a kernel of its own, not a loop of einsums:
    the forward's custom call alone does not pass, and a tile the v5e's
    compiler refuses (VMEM at head 256 over 4,096 positions) fails
    here."""
    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=causal, interpret=False)
        return jnp.sum(o.astype(jnp.float32))

    grad = jax.grad(loss, argnums=(0, 1, 2))
    text = _compiled_text(grad, shape, dtype, v5e)
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2, len(calls)       # the forward and the backward
    assert sum("flash_attention_bwd" in line for line in calls) == 1
    assert " while(" not in text


def test_expert_layer_compiles_to_the_chips_grouped_kernels(v5e):
    """``MoEExperts``' mathematics at glm-4.7-flash's widths (4,096 rows
    x 4 entries, 8 of 64 experts held): forward and gradient compile for
    the v5e, and the three grouped products are the compiler's own
    ragged-dot kernels, which walk the live row tiles, not a dense
    product an expert over the whole worst-case buffer."""
    from mxnet_tpu.parallel import moe

    def loss(x, idx, w, wg, wu, wd):
        y, count = moe.moe_apply_held(x, idx, w, wg, wu, wd, 0, 64)
        return jnp.sum(y.astype(jnp.float32)) + jnp.sum(count)

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    text = jax.jit(jax.grad(loss, argnums=(0, 2, 3, 4, 5))).lower(
        spec((4096, 2048)), spec((4096, 4), jnp.int32),
        spec((4096, 4), jnp.float32), spec((8, 1536, 2048)),
        spec((8, 1536, 2048)), spec((8, 2048, 1536))).compile().as_text()
    assert text.count("ragged-dot-metadata") >= 3
    assert "tpu_custom_call" in text
