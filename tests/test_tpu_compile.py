"""Compile rehearsals for a TPU v5e chip, at real widths, with no chip.

The three Pallas kernels are compiled for a *described* v5e chip: the TPU
compiler refuses here what interpret mode accepts (blocks off the (8, 128)
tiling, unpacked dynamic slices, too much VMEM).  Nothing runs, so these
say nothing about results or times.

The topology is described inside a module fixture — never at import — so
that under several pytest workers only the worker that runs this file
loads the TPU library.  The persistent compilation cache is off around
these compiles: an entry compiled for a described chip cannot be read back
without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_arch
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rglru_scan import rglru
from repro.kernels.rwkv6_scan import wkv6


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    saved_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"   # else the compiler logs in /tmp
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001  (no TPU compiler installed)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()
        if saved_log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = saved_log_dir


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _starcoder2_qkv(batch, seq):
    c = get_arch("starcoder2-3b")
    bf = jnp.bfloat16
    return [((batch, seq, c.n_heads, c.d_head), bf),
            ((batch, seq, c.n_kv_heads, c.d_head), bf),
            ((batch, seq, c.n_kv_heads, c.d_head), bf)]


# train step (4 x 2048) and serve prefill (8 x 1024) shapes
@pytest.mark.parametrize("batch,seq", [(4, 2048), (8, 1024)])
def test_flash_forward_compiles_for_v5e(one_chip, batch, seq):
    _compile(lambda q, k, v: flash_attention(q, k, v, causal=True),
             one_chip, *_starcoder2_qkv(batch, seq))


def test_flash_vjp_compiles_for_v5e(one_chip):
    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    c = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                 *_starcoder2_qkv(4, 2048))
    assert c.memory_analysis().temp_size_in_bytes < 2 * 2**30


# prefill (no incoming state) and a one-token decode step (with state)
@pytest.mark.parametrize("seq,with_state", [(512, False), (1, True)])
def test_wkv6_compiles_for_v5e(one_chip, seq, with_state):
    c = get_arch("rwkv6-7b")
    H, D = c.d_model // c.rwkv.head_dim, c.rwkv.head_dim
    x = ((2, seq, H, D), jnp.bfloat16)
    shapes = [x, x, x, x, ((H, D), jnp.bfloat16)]
    if with_state:
        shapes.append(((2, H, D, D), jnp.float32))
    _compile(lambda *a: wkv6(*a), one_chip, *shapes)


def test_rglru_compiles_for_v5e(one_chip):
    W = get_arch("recurrentgemma-9b").rglru.lru_width
    _compile(lambda x, la, h0: rglru(x, la, h0), one_chip,
             ((2, 2048, W), jnp.bfloat16), ((2, 2048, W), jnp.float32),
             ((2, W), jnp.float32))
