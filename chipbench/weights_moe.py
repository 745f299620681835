"""Weights of a sparse-expert decoder made by the benchmark from the seed,
as ``chipbench/weights.py`` makes a dense one: one jitted call, on the
device, in the type they are served in.

The tree is the program's for a pre-norm decoder whose layers repeat the
configuration's ``layer_types`` period: ``groups[0].p<i>`` holds the
period's layer i stacked over the periods, each with q, k, v, o
projections, a router (d, experts) and SwiGLU experts (gate, up: (experts,
d, f); down: (experts, f, d)); an untied lm head.  The reference
(``chipbench/reference_moe.py``) makes the same weights again from the
same seed."""
from __future__ import annotations

import jax

from chipbench import weights

# the configuration's layer types, as the program names their attention
KINDS = {"sliding_attention": "local", "full_attention": "global"}


def period(m: dict) -> list[str]:
    """The shortest run of ``layer_types`` whose repeats make all of it."""
    types = m["layer_types"][:m["num_hidden_layers"]]
    return next(types[:n] for n in range(1, len(types) + 1)
                if types == types[:n] * (len(types) // n))


def layout(m: dict) -> dict:
    """Tree of ``(shape, fan_in)`` (``chipbench/weights.py``'s convention)."""
    d, H, KV, Dh = (m["hidden_size"], m["num_attention_heads"],
                    m["num_key_value_heads"], m["head_dim"])
    f, E, V = m["moe_intermediate_size"], m["num_experts"], m["vocab_size"]
    n = len(period(m))
    R = m["num_hidden_layers"] // n
    layer = {
        "ln1": ((R, d), 0),
        "attn": {"wq": ((R, d, H, Dh), d), "wk": ((R, d, KV, Dh), d),
                 "wv": ((R, d, KV, Dh), d), "wo": ((R, H, Dh, d), H * Dh)},
        "ln2": ((R, d), 0),
        "moe": {"router": ((R, d, E), d), "w_gate": ((R, E, d, f), d),
                "w_up": ((R, E, d, f), d), "w_down": ((R, E, f, d), f)},
    }
    return {"embed": ((V, d), 1),
            "groups": [{f"p{i}": layer for i in range(n)}],
            "ln_f": ((d,), 0), "lm_head": ((d, V), d)}


def _flat(m: dict):
    flat, tree = jax.tree_util.tree_flatten(layout(m), is_leaf=weights._is_leaf)
    return tuple(flat), tree


def make(m: dict, seed: int, dtype):
    """Every weight of the model, from ``seed``, in one jitted call."""
    flat, tree = _flat(m)
    return jax.tree_util.tree_unflatten(
        tree, weights._make_fn(flat, dtype)(weights._root(seed)))
