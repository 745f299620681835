"""Operations and bytes worked out from shapes: the yardstick of every
utilization and roofline share.  Each function takes the model
configuration as its JSON file holds it (``chipbench/configs``)."""


def dims(m: dict) -> tuple[int, int, int, int, int, int, int]:
    """(d_model, heads, kv heads, head dim, ffn width, vocab, layers)."""
    return (m["hidden_size"], m["num_attention_heads"],
            m["num_key_value_heads"], m["head_dim"],
            m["intermediate_size"], m["vocab_size"], m["num_hidden_layers"])


def layer_matmul_params(m: dict) -> int:
    """Weights one token meets in the matmuls of one layer: q, k, v, o and
    the two matrices of the (non-gated) MLP."""
    d, H, KV, Dh, f, _, _ = dims(m)
    return d * H * Dh + 2 * d * KV * Dh + H * Dh * d + 2 * d * f


def causal_pairs(s: int) -> int:
    """(query, key) pairs a causal mask keeps in a sequence of ``s``."""
    return s * (s + 1) // 2
