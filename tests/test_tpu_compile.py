"""Compile rehearsals for a TPU v5e chip, at real widths, with no chip.

The Pallas kernels are compiled for a *described* v5e chip: the TPU
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
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_arch
from repro.kernels import ops
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


# the serve cells' prefill shapes: granite-20b's causal MQA (48 heads over
# one kv head) at 1 x 8192, and mellum2-12b's sliding layers (GQA 32/4,
# window 1024) at 8 x 4096
@pytest.mark.parametrize("arch,batch,seq,window",
                         [("granite-20b", 1, 8192, 0),
                          ("mellum2-12b", 8, 4096, 1024)])
def test_flash_forward_compiles_for_v5e_at_serve_shapes(one_chip, arch, batch,
                                                        seq, window):
    c = get_arch(arch)
    q = ((batch, seq, c.n_heads, c.d_head), jnp.bfloat16)
    kv = ((batch, seq, c.n_kv_heads, c.d_head), jnp.bfloat16)
    _compile(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                             window=window),
             one_chip, q, kv, kv)


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


# mellum2-12b's experts (d 2304, f 896, 64 of them): a decode step's 64
# routes (8 tokens x top 8) through gate/up and down, and a prefill's
# 8 x 4096 x 8
@pytest.mark.parametrize("rows,k,n", [(64, 2304, 896), (64, 896, 2304),
                                      (262144, 2304, 896)])
def test_gmm_compiles_for_v5e(one_chip, rows, k, n):
    _compile(ops.pallas_gmm, one_chip, ((rows, k), jnp.bfloat16),
             ((64, k, n), jnp.bfloat16), ((64,), jnp.int32))


def _decode_cut(name: str, sharding):
    """The serve cells' decode cuts as shapes on ``sharding``: mellum2-12b's
    8 layers (two periods of three sliding layers and a full one) at batch
    8 against a 4224-slot global cache, granite-20b's 13 layers at batch 8
    against 2048 + 128 slots.  Returns (cfg, params, cache, tokens)."""
    from repro.models import params as pmod
    from repro.models import transformer

    base = get_arch(name)
    if name == "mellum2-12b":
        cfg = base.replace(n_layers=8,
                           block_groups=((base.block_groups[0][0], 2),))
        slots = 4096 + 128
    else:
        cfg = base.replace(n_layers=13, block_groups=((("global",), 13),))
        slots = 2048 + 128

    def shapes(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=sharding), tree)

    params = shapes(pmod.abstract(pmod.cast_defs(
        transformer.model_defs(cfg), jnp.bfloat16)))
    cache = shapes(jax.eval_shape(
        lambda: transformer.init_cache(cfg, 8, slots)))
    tok = jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=sharding)
    return cfg, params, cache, tok


def test_mellum_decode_step_compiles_for_v5e(one_chip, monkeypatch):
    """The decode step of the chip cell's cut, as ``Server`` jits it, with
    the grouped matmul on its Pallas path."""
    from repro.runtime.serve_loop import decode_jit

    monkeypatch.setattr(ops, "use_pallas", lambda: True)
    cfg, params, cache, tok = _decode_cut("mellum2-12b", one_chip)
    compiled = decode_jit(cfg).lower(params, cache, tok).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') \
        >= 3   # gate, up and down of one pattern position at least
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


def _hlo_computations(text: str) -> dict:
    """{computation name: {instruction name: (result type, opcode,
    operand names)}, with the root's name under None}."""
    comps, body = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", line)
        if head:
            body = comps.setdefault(head.group(1), {})
            continue
        ins = re.match(r"\s*(ROOT )?%([\w.\-]+) = (.+?) ([a-z][\w\-]*)\((.*)",
                       line)
        if body is None or not ins:
            continue
        operands = re.findall(r"%([\w.\-]+)", ins.group(5).split(")")[0])
        calls = re.search(r"calls=%([\w.\-]+)", line)
        body[ins.group(2)] = (ins.group(3), ins.group(4), operands,
                              calls.group(1) if calls else None)
        if ins.group(1):
            body[None] = ins.group(2)
    return comps


def _writes_one_slot(comps: dict, fused: str, slot: int) -> bool:
    """Whether computation ``fused`` ends in a dynamic-update-slice of
    ``slot`` elements into its first operand: a write in place."""
    body = comps[fused]
    _, op, operands, _ = body[body[None]]
    if op != "dynamic-update-slice":
        return False
    dims = re.search(r"\[([\d,]*)\]", body[operands[1]][0]).group(1)
    return int(np.prod([int(d) for d in dims.split(",") if d])) == slot


@pytest.mark.parametrize("arch", ["mellum2-12b", "granite-20b"])
def test_decode_step_writes_its_cache_in_place_for_v5e(one_chip, monkeypatch,
                                                       arch):
    """The decode step as ``Server`` jits it aliases its whole K/V cache,
    and no copy, and no fusion but the one-slot write, has a stacked K/V
    array as its result: each layer's K and V are read where they lie and
    written one slot in place."""
    from repro.runtime.serve_loop import decode_jit

    monkeypatch.setattr(ops, "use_pallas", lambda: True)
    cfg, params, cache, tok = _decode_cut(arch, one_chip)
    compiled = decode_jit(cfg).lower(params, cache, tok).compile()
    mem = compiled.memory_analysis()
    kv = [x for g in cache["groups"] for ent in g.values()
          for n, x in ent.items() if n in ("k", "v")]
    assert mem.alias_size_in_bytes >= sum(x.size * x.dtype.itemsize
                                          for x in kv)
    if arch == "mellum2-12b":
        assert mem.temp_size_in_bytes < 64 * 2**20
    stacks = {"bf16[%s]" % ",".join(map(str, x.shape)) for x in kv}
    slot = cfg.n_kv_heads * 8 * cfg.d_head
    comps = _hlo_computations(compiled.as_text())
    offending = [
        (name, op) for body in comps.values()
        for name, ins in body.items() if name is not None
        for result, op, _, calls in [ins]
        if any(s in result for s in stacks)
        and (op in ("copy", "copy-start")
             or op == "fusion" and not _writes_one_slot(comps, calls, slot))]
    assert not offending, offending
