"""One call of the flash-attention forward kernel, causal, with GQA.

FLOPs: two matmuls (q k^T and p v) over the kept (query, key) pairs,
2 * D multiply-adds each, per query head.  Bytes: q, k, v read once and
o written once in bf16, the per-row log-sum-exp written in f32: the least
traffic any kernel computing the same outputs needs."""
from chipbench.counts import causal_pairs


def flops(batch: int, seq: int, heads: int, head_dim: int) -> float:
    return 4.0 * batch * heads * head_dim * causal_pairs(seq)


def bytes_moved(batch: int, seq: int, heads: int, kv_heads: int,
                head_dim: int) -> float:
    bf16, f32 = 2, 4
    qo = 2 * batch * seq * heads * head_dim * bf16
    kv = 2 * batch * seq * kv_heads * head_dim * bf16
    return float(qo + kv + batch * heads * seq * f32)
