"""mellum2-12b — JetBrains' code-completion MoE: 64 experts top-8 in every
layer, 3:1 sliding/full attention, YaRN on the full layers.

[hf:JetBrains/Mellum2-12B-A2.5B-Instruct config.json]

28 layers = 7 x (3 sliding + 1 full).  Sliding window 1024.  Every MLP is
sparse: softmax router, top-8, renormalised (``norm_topk_prob``), SwiGLU
experts of width 896, no shared expert; the dense ``intermediate_size``
(7168) is unused.  Routing is dropless.  RoPE theta 500000 everywhere;
the full layers scale it by YaRN (factor 16 over 8192 positions).  No
query/key norm.  The checkpoint's MTP head is not run (the server does
not speculate).
"""
from repro.configs.base import ArchConfig, MoESpec, YaRNSpec, register

register(
    ArchConfig(
        name="mellum2-12b",
        family="moe",
        n_layers=28,
        d_model=2304,
        n_heads=32,
        n_kv_heads=4,
        d_head=128,
        d_ff=896,  # the experts' width
        vocab_size=98304,
        block_groups=((("local", "local", "local", "global"), 7),),
        window=1024,
        moe=MoESpec(n_experts=64, top_k=8, capacity_factor=None),
        rope_theta=500_000.0,
        yarn=YaRNSpec(factor=16.0, original_max_position=8192,
                      beta_fast=32.0, beta_slow=1.0,
                      attention_factor=1.2772588722239782),
        norm_eps=1e-6,
        notes="fine-grained dropless MoE; sliding + full attention with YaRN",
        source="hf:JetBrains/Mellum2-12B-A2.5B-Instruct",
    )
)
