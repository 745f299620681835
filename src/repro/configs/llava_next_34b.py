"""llava-next-34b — VLM text backbone (Yi-34B-class), anyres tiling stubbed.

[hf:llava-hf/llava-v1.6-mistral-7b-hf; unverified]

The vision tower is a STUB per assignment: ``input_specs()`` provides
precomputed patch embeddings (batch, n_patches, d_model) that are prepended
to the text-token embeddings (anyres tiling produces up to 5 tiles x 576
patches; we provision one base tile by default).
"""
from repro.configs.base import ArchConfig, register

register(
    ArchConfig(
        name="llava-next-34b",
        family="vlm",
        n_layers=60,
        d_model=7168,
        n_heads=56,
        n_kv_heads=8,
        d_head=128,
        d_ff=20480,
        vocab_size=64000,
        block_groups=((("global",), 60),),
        n_patches=576,
        rope_theta=5_000_000.0,
        notes="patch embeddings occupy the first 576 positions of the sequence",
        source="hf:llava-hf/llava-v1.6-34b-hf",
    )
)
