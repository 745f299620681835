"""One decode step of a batch: one new token per request against a KV
cache holding ``context`` positions (the new one included).

FLOPs: 2 per weight per token, and 4 * D per cached position and query
head.  Bytes: every matmul weight and the lm head once in bf16, the
embedding rows of the batch, the final norm and the layers' norms, and
the K and V of the ``context`` valid positions of each layer: the least
an exact step must read.  Writes (one K/V slot, the logits) are
counted too."""
from chipbench.counts import dims, layer_matmul_params


def flops(m: dict, batch: int, context: float) -> float:
    d, H, _, Dh, _, V, L = dims(m)
    weights = L * layer_matmul_params(m) + d * V
    return 2.0 * weights * batch + 4.0 * batch * H * Dh * context * L


def bytes_moved(m: dict, batch: int, context: float) -> float:
    d, H, KV, Dh, _, V, L = dims(m)
    bf16, f32 = 2, 4
    weights = (L * (layer_matmul_params(m) + 2 * d) + d * V + d) * bf16
    embed_rows = batch * d * bf16
    kv_read = 2.0 * L * batch * context * KV * Dh * bf16
    kv_write = 2 * L * batch * KV * Dh * bf16
    logits = batch * V * f32
    return weights + embed_rows + kv_read + kv_write + logits
