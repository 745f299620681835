"""Weights made by the benchmark from the seed: one jitted call, on the
device, in the type they are served or trained in.

The tree is that of a pre-norm dense decoder with a non-gated MLP and an
untied lm head, stacked over layers, and laid out as the program's
parameter tree names it (``groups[0].p0`` holds the stacked layers).  The
program gets these weights in place of its own initializer's, and the
reference (``chipbench/reference.py``) makes the same ones again from the
same seed, so neither takes anything the other made.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.counts import dims


def seed32(seed: int, *salt) -> int:
    """A 32-bit seed for JAX or numpy from any whole ``seed`` and salts."""
    words = [seed] + [s if isinstance(s, int) else
                      int.from_bytes(str(s).encode(), "little") for s in salt]
    return int(np.random.SeedSequence(words).generate_state(1)[0])


def layout(m: dict) -> dict:
    """Tree of ``(shape, fan_in)``; ``fan_in`` 0 marks a norm scale (ones).
    A matrix is drawn from N(0, 1 / fan_in); the embedding from N(0, 1)."""
    d, H, KV, Dh, f, V, L = dims(m)
    layer = {
        "ln1": ((L, d), 0),
        "attn": {"wq": ((L, d, H, Dh), d), "wk": ((L, d, KV, Dh), d),
                 "wv": ((L, d, KV, Dh), d), "wo": ((L, H, Dh, d), H * Dh)},
        "ln2": ((L, d), 0),
        "ffn": {"w_up": ((L, d, f), d), "w_down": ((L, f, d), f)},
    }
    return {"embed": ((V, d), 1), "groups": [{"p0": layer}],
            "ln_f": ((d,), 0), "lm_head": ((d, V), d)}


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def _draw(key, shape, fan_in, dtype):
    if fan_in == 0:
        return jnp.ones(shape, dtype)
    x = jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
    return x.astype(dtype)


def _root(seed: int):
    return jax.random.key(seed32(seed, "weights"))


def _flat(m: dict):
    flat, tree = jax.tree_util.tree_flatten(layout(m), is_leaf=_is_leaf)
    return tuple(flat), tree


@functools.lru_cache(maxsize=None)
def _make_fn(flat: tuple, dtype):
    # the seed enters as an argument, so one compiled program (and one
    # entry of the persistent cache) serves every seed
    @jax.jit
    def draw_all(root):
        return [_draw(jax.random.fold_in(root, i), s, f, dtype)
                for i, (s, f) in enumerate(flat)]
    return draw_all


def tree_of(m: dict):
    return _flat(m)[1]


def make(m: dict, seed: int, dtype):
    """Every weight of the model, from ``seed``, in one jitted call."""
    flat, tree = _flat(m)
    return jax.tree_util.tree_unflatten(tree, _make_fn(flat, dtype)(_root(seed)))


@functools.lru_cache(maxsize=None)
def _leaf_fn(shape, fan_in, dtype):
    return jax.jit(lambda root, i: _draw(jax.random.fold_in(root, i), shape,
                                         fan_in, dtype))


def leaf(m: dict, seed: int, index: int, dtype):
    """Leaf ``index`` of ``make(m, seed, dtype)``, made alone."""
    shape, fan_in = _flat(m)[0][index]
    return _leaf_fn(shape, fan_in, dtype)(_root(seed), index)


def check_matches(params, abstract) -> None:
    """Refuse weights whose tree, shapes or types differ from the
    program's own parameter tree (``abstract``: ShapeDtypeStructs)."""
    got = jax.tree_util.tree_structure(params)
    want = jax.tree_util.tree_structure(abstract)
    if got != want:
        raise ValueError(f"weight tree differs from the program's: {got} "
                         f"vs {want}")
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(abstract)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"weight {a.shape} {a.dtype} where the program "
                             f"has {b.shape} {b.dtype}")
