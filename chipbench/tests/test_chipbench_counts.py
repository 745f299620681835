"""Each count function against a hand count at one shape."""
import json
import pathlib

import pytest

from chipbench.counts import decode_step, flash_fwd, prefill, train_step

ROOT = pathlib.Path(__file__).resolve().parents[1]
# d 8, 2 heads of 4 (q), 1 kv head, MLP 16, vocab 10, 3 layers
TINY = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
        "head_dim": 4, "intermediate_size": 16, "vocab_size": 10,
        "num_hidden_layers": 3}
# per layer: q 8*2*4=64, k 8*1*4=32, v 32, o 64, MLP 2*8*16=256 -> 448
LAYER = 448


def test_flash_forward_by_hand():
    # batch 1, seq 4: 10 causal pairs; 2 heads of 8; 2 matmuls of 2*8 each
    assert flash_fwd.flops(1, 4, 2, 8) == 4 * 2 * 8 * 10
    # q and o: 2 * 1*4*2*8 * 2 B; k and v: 2 * 1*4*1*8 * 2 B; lse 2*4 * 4 B
    assert flash_fwd.bytes_moved(1, 4, 2, 1, 8) == 256 + 128 + 32


def test_train_step_by_hand():
    # batch 2, seq 5: 10 tokens; N = 3 * 448 + 8 * 10 (lm head)
    n = 3 * LAYER + 80
    attn = 3 * 4 * 2 * 2 * 4 * 15 * 3   # 3x fwd, 15 pairs, 2 heads of 4
    assert train_step.flops(TINY, 2, 5) == 6 * n * 10 + attn


def test_prefill_by_hand():
    layers = 2 * 3 * LAYER * 2 * 5
    attn = 4 * 2 * 2 * 4 * 15 * 3
    head = 2 * 8 * 10 * 2                # the last position of 2 prompts
    assert prefill.flops(TINY, 2, 5) == layers + attn + head


def test_decode_step_by_hand():
    assert decode_step.flops(TINY, 2, 6) == \
        2 * (3 * LAYER + 80) * 2 + 4 * 2 * 2 * 4 * 6 * 3
    weights = (3 * (LAYER + 16) + 80 + 8) * 2      # bf16, norms included
    embed_rows = 2 * 8 * 2
    kv_read = 2 * 3 * 2 * 6 * 1 * 4 * 2
    kv_write = 2 * 3 * 2 * 1 * 4 * 2
    logits = 2 * 10 * 4
    assert decode_step.bytes_moved(TINY, 2, 6) == \
        weights + embed_rows + kv_read + kv_write + logits


def test_real_sizes_match_the_published_reckoning():
    with open(ROOT / "configs" / "sc2-3b-4l.json") as f:
        sc2 = json.load(f)
    with open(ROOT / "configs" / "granite-20b-13l.json") as f:
        granite = json.load(f)
    # 6 * 534.8 M matmul weights * 8192 tokens + causal attention
    assert train_step.flops(sc2, 2, 4096) == pytest.approx(2.877e13, rel=1e-3)
    # 13 layers of 379 M weights + the lm head, read once in bf16
    assert decode_step.bytes_moved(granite, 8, 2112.5) == pytest.approx(
        1.058e10, rel=1e-2)
