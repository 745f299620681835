"""Model FLOPs of one training step (forward and backward).

6 * N * tokens, with N the weights of every matmul a token meets (the
layers' q, k, v, o and MLP matrices, and the lm head); the embedding
lookup is a gather and is not counted.  Causal attention adds 3 x the
forward's 4 * D FLOPs per kept (query, key) pair and query head.  Work a
rematerialization repeats is not counted."""
from chipbench.counts import causal_pairs, dims, layer_matmul_params


def flops(m: dict, batch: int, seq: int) -> float:
    d, H, _, Dh, _, V, L = dims(m)
    n = L * layer_matmul_params(m) + d * V
    attn = 3 * 4 * batch * H * Dh * causal_pairs(seq) * L
    return 6.0 * n * batch * seq + attn
