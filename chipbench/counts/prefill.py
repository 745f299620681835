"""Model FLOPs of one prefill: the layers over every prompt token,
causal attention over the prompt, and the lm head for the last position
only (the one whose logits prefill returns)."""
from chipbench.counts import causal_pairs, dims, layer_matmul_params


def flops(m: dict, batch: int, prompt: int) -> float:
    d, H, _, Dh, _, V, L = dims(m)
    layers = 2.0 * L * layer_matmul_params(m) * batch * prompt
    attn = 4.0 * batch * H * Dh * causal_pairs(prompt) * L
    return layers + attn + 2.0 * d * V * batch
