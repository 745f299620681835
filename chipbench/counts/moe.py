"""Operations and bytes of the sparse-expert decoder (the MoE cell's
configuration file): prefill, a decode step and one grouped matmul.

Per token a layer meets its q, k, v and o projections, its router and
the SwiGLU weights (gate, up, down) of the ``num_experts_per_tok``
experts it is routed to.  A sliding layer attends to at most
``sliding_window`` positions, a full layer to the whole prefix.  A decode
step reads the weights of only the experts the batch's tokens touched:
``touched`` is their mean number per layer (the program counts it)."""
from chipbench.counts import causal_pairs


def dims(m: dict):
    """(d_model, heads, kv heads, head dim, expert width, experts,
    experts a token, vocab, layers, window)."""
    return (m["hidden_size"], m["num_attention_heads"],
            m["num_key_value_heads"], m["head_dim"],
            m["moe_intermediate_size"], m["num_experts"],
            m["num_experts_per_tok"], m["vocab_size"],
            m["num_hidden_layers"], m["sliding_window"])


def attn_params(m: dict) -> int:
    d, H, KV, Dh = dims(m)[:4]
    return 2 * d * H * Dh + 2 * d * KV * Dh


def expert_params(m: dict) -> int:
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    return 3 * d * f


def active_layer_params(m: dict) -> int:
    """Matmul weights one token meets in one layer."""
    d, E, K = m["hidden_size"], m["num_experts"], m["num_experts_per_tok"]
    return attn_params(m) + d * E + K * expert_params(m)


def layer_windows(m: dict) -> list[int]:
    """Per layer, the positions a query may attend to (0: all)."""
    W = m["sliding_window"]
    return [W if t == "sliding_attention" else 0
            for t in m["layer_types"][:m["num_hidden_layers"]]]


def window_pairs(s: int, w: int) -> int:
    """(query, key) pairs of a causal mask over ``s`` positions in which
    each query sees at most ``w`` keys (itself included)."""
    if not w or w >= s:
        return causal_pairs(s)
    return causal_pairs(w) + (s - w) * w


def flash_flops(batch: int, seq: int, heads: int, head_dim: int,
                window: int) -> float:
    """One flash-forward call over ``seq`` positions: q k^T and p v over
    the causal pairs a query may see (at most ``window`` of them; 0: all)."""
    return 4.0 * batch * heads * head_dim * window_pairs(seq, window)


def prefill_flops(m: dict, batch: int, prompt: int) -> float:
    """The layers over every prompt token, window-limited causal
    attention, and the lm head for the last position only."""
    d, H, _, Dh, _, _, _, V, L, _ = dims(m)
    layers = 2.0 * L * active_layer_params(m) * batch * prompt
    pairs = sum(window_pairs(prompt, w) for w in layer_windows(m))
    return layers + 4.0 * batch * H * Dh * pairs + 2.0 * d * V * batch


def attended(m: dict, context: float) -> float:
    """Cached positions one decode query reads, summed over layers."""
    return sum(min(context, w) if w else context for w in layer_windows(m))


def decode_flops(m: dict, batch: int, context: float) -> float:
    d, H, _, Dh, _, _, _, V, L, _ = dims(m)
    weights = L * active_layer_params(m) + d * V
    return 2.0 * weights * batch + 4.0 * batch * H * Dh * attended(m, context)


def decode_bytes(m: dict, batch: int, context: float, touched: float) -> float:
    """What an exact decode step must read and write: attention weights,
    router, norms, the touched experts, the lm head and the embedding
    rows, the valid K and V (a sliding layer's up to its window); one K/V
    slot and the float32 logits written."""
    d, H, KV, Dh, _, E, _, V, L, _ = dims(m)
    bf16, f32 = 2, 4
    weights = (L * (attn_params(m) + d * E + 2 * d
                    + touched * expert_params(m)) + d * V + d) * bf16
    embed_rows = batch * d * bf16
    kv_read = 2.0 * batch * KV * Dh * bf16 * attended(m, context)
    kv_write = 2 * L * batch * KV * Dh * bf16
    return weights + embed_rows + kv_read + kv_write + batch * V * f32


def gmm_flops(rows: int, k: int, n: int) -> float:
    return 2.0 * rows * k * n


def gmm_bytes(rows: int, k: int, n: int, groups: float) -> float:
    """bf16 weights of the ``groups`` experts that received rows, the rows
    in and the rows out."""
    return 2.0 * (groups * k * n + rows * k + rows * n)
