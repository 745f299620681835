"""The sparse-expert readers (``gmm_roofline.moe``,
``decode_hbm_roofline.moe``, ``mfu.moe``, ``flash_roofline.moe``) and
their counts, on a
synthetic trace summary and hand-built span records."""
import json
import pathlib
import sys

import pytest

from chipbench import harness, trace
from chipbench.counts import flash_fwd, moe

ROOT = pathlib.Path(__file__).resolve().parents[2]
NAMES = ("gmm_roofline.moe", "decode_hbm_roofline.moe", "mfu.moe",
         "flash_roofline.moe")
PEAKS = {"bf16_flops_per_s": 200e12, "hbm_bytes_per_s": 800e9}
TRAFFIC = {"batch": 8, "prompt_len": 4096, "new_tokens": 128}
# d 8, 2 heads of 4 over 1 KV head, 4 experts of width 2, top 2, vocab 10,
# 4 layers (3 sliding of window 3, 1 full)
TINY = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
        "head_dim": 4, "moe_intermediate_size": 2, "num_experts": 4,
        "num_experts_per_tok": 2, "vocab_size": 10, "num_hidden_layers": 4,
        "sliding_window": 3,
        "layer_types": ["sliding_attention"] * 3 + ["full_attention"]}


def model():
    with open(ROOT / "chipbench" / "configs" / "mellum2-12b-8l.json") as f:
        return json.load(f)


def _gmm(name, rows, k, n, E=128):
    return (f"%{name} = bf16[{rows},{n}]{{1,0:T(8,128)(2,1)}} custom-call("
            f"s32[65]{{0}} %a, s32[128]{{0}} %b, s32[128]{{0}} %c, "
            f"s32[1]{{0}} %d, bf16[{rows},{k}]{{1,0}} %x, "
            f"bf16[{E},{k},{n}]{{2,1,0}} %w), "
            f'custom_call_target="tpu_custom_call"')


def summary(ops=None, modules=None):
    ops = {
        # decode: gate and up (64 rows, d -> f), down (f -> d), 10 runs each
        _gmm("gate", 64, 2304, 896): [0.060, 10],
        _gmm("up", 64, 2304, 896): [0.060, 10],
        _gmm("down", 64, 896, 2304): [0.060, 10],
        # prefill's rows are left out; so are other kernels and fusions
        _gmm("pre", 262144, 2304, 896): [5.0, 2],
        # one layer's experts alone count too; a 48-group operand does not
        _gmm("one", 64, 2304, 896, E=64): [0.020, 10],
        _gmm("other", 64, 2304, 896, E=48): [1.0, 10],
        "%fusion.1 = bf16[64,896]{1,0} fusion(bf16[64,896] %p)": [1.0, 10],
        ("%flash.2 = (bf16[8,32,4096,128]{3,2,1,0}, f32[8,32,4096,1]) "
         'custom-call(bf16[64,2304,896] %w), '
         'custom_call_target="tpu_custom_call"'): [1.0, 1],
    } if ops is None else ops
    modules = {"jit_prefill_step": [0.5, 0.5],
               "jit_serve_step": [0.008] * 256} if modules is None else modules
    return trace.Summary(window_s=3.0, busy_s=2.99, n_chips=1,
                         modules=modules, ops=ops, spans={},
                         idle_by_span={}, gaps=[])


@pytest.fixture
def spans(monkeypatch):
    from repro.obs import spans

    held = []
    monkeypatch.setattr(spans, "captured", lambda: list(held))
    return spans, held


def _moe_spans(spans, touched=(40.0, 44.0)):
    return [spans.Record("repro.serve.moe", i, i + 1,
                         {"batch": i, "experts_touched": t,
                          "max_load_over_mean": 1.5, "decode_steps": 127},
                         "repro.serve.run") for i, t in enumerate(touched)]


def read(name, s, m=None):
    rec = harness.Record(s, m or model(), TRAFFIC, PEAKS, {})
    return harness.reader(ROOT, name)(rec)


def test_counts_by_hand():
    # per layer: q 8*2*4, k 8*4, v 8*4, o 2*4*8 = 192; router 8*4 = 32;
    # an expert 3*8*2 = 48, 2 a token
    assert moe.attn_params(TINY) == 192 and moe.expert_params(TINY) == 48
    assert moe.active_layer_params(TINY) == 192 + 32 + 96
    # 5 positions: a window of 3 keeps 1+2+3+3+3 = 12 pairs, full 15
    assert moe.window_pairs(5, 3) == 12 and moe.window_pairs(5, 0) == 15
    assert moe.prefill_flops(TINY, 2, 5) == \
        2 * 4 * 320 * 2 * 5 + 4 * 2 * 2 * 4 * (3 * 12 + 15) + 2 * 8 * 10 * 2
    # a decode query at context 6 reads 3 + 3 + 3 + 6 cached positions
    assert moe.attended(TINY, 6) == 15
    assert moe.decode_flops(TINY, 2, 6) == \
        2 * (4 * 320 + 80) * 2 + 4 * 2 * 2 * 4 * 15
    weights = (4 * (192 + 32 + 16 + 3 * 48) + 80 + 8) * 2
    kv = 2 * 2 * 1 * 4 * 2 * 15 + 2 * 4 * 2 * 1 * 4 * 2
    assert moe.decode_bytes(TINY, 2, 6, 3) == \
        weights + 2 * 8 * 2 + kv + 2 * 10 * 4
    assert moe.gmm_bytes(4, 8, 2, 3) == 2 * (3 * 16 + 32 + 8)


def test_real_sizes_match_the_published_reckoning():
    m = model()
    # 21.23 M attention, 0.15 M router, 396.4 M experts a layer
    assert moe.attn_params(m) == pytest.approx(21.23e6, rel=1e-3)
    assert 64 * moe.expert_params(m) == pytest.approx(396.4e6, rel=1e-3)
    # about 1.29 GFLOP a prefill token; 5.2 GB an exact decode step at 42
    # touched experts, 7.4 GB reading all 64
    assert moe.prefill_flops(m, 8, 4096) / (8 * 4096) == pytest.approx(
        1.29e9, rel=1e-2)
    assert moe.decode_bytes(m, 8, 4160.5, 42.07) == pytest.approx(5.20e9,
                                                                  rel=1e-2)
    assert moe.decode_bytes(m, 8, 4160.5, 64) == pytest.approx(7.38e9,
                                                               rel=1e-2)


def test_gmm_roofline_reads_the_decode_calls(spans):
    mod, held = spans
    held += _moe_spans(mod)
    m = model()
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    # 40 calls of 42 touched experts' d x f weights plus 64 rows in and
    # out: bytes bind; 0.2 s
    per_call = 2.0 * (42 * d * f + 64 * d + 64 * f) / PEAKS["hbm_bytes_per_s"]
    assert read("gmm_roofline.moe", summary()) == pytest.approx(
        100 * 40 * per_call / 0.2)


def test_decode_hbm_roofline_and_mfu(spans):
    mod, held = spans
    held += _moe_spans(mod)
    m = model()
    ctx = 4096 + 129 / 2
    assert read("decode_hbm_roofline.moe", summary()) == pytest.approx(
        100 * moe.decode_bytes(m, 8, ctx, 42.0) / 800e9 / 0.008)
    work = 2 * moe.prefill_flops(m, 8, 4096) \
        + 256 * moe.decode_flops(m, 8, ctx)
    assert read("mfu.moe", summary()) == pytest.approx(
        100 * work / (3.0 * 200e12))


def _flash(name, B=8, H=32, S=4096, D=128):
    return (f"%{name} = (bf16[{B},{H},{S},{D}]{{3,2,1,0}}, "
            f"f32[{B},{H},{S},1]{{3,2,1,0}}) custom-call(bf16[{B},{S},{H},{D}]"
            f' %q), custom_call_target="tpu_custom_call"')


def test_flash_roofline_counts_window_limited_pairs():
    m = model()
    B, S, H, KV, D = 8, 4096, 32, 4, 128
    # two prefills of 8 layers (3 sliding of window 1024 : 1 full); the
    # other shapes (granite's 48 heads, a shorter prompt) are not its calls
    ops = {_flash("flash.1"): [0.3, 12], _flash("flash.2"): [0.1, 4],
           _flash("dense", H=48): [9.0, 4], _flash("short", S=2048): [9.0, 4]}
    sliding = 4.0 * B * H * D * (1024 * 1025 // 2 + (S - 1024) * 1024)
    full = 4.0 * B * H * D * S * (S + 1) // 2
    assert moe.flash_flops(B, S, H, D, 1024) == sliding
    assert moe.flash_flops(B, S, H, D, 0) == full
    # FLOPs bind at this shape
    assert sliding / 200e12 > flash_fwd.bytes_moved(B, S, H, KV, D) / 800e9
    least = 2 * (6 * sliding + 2 * full) / 200e12
    assert read("flash_roofline.moe", summary(ops=ops)) == pytest.approx(
        100 * least / 0.4)


@pytest.mark.parametrize("name", NAMES)
def test_no_trace_reads_none(spans, name):
    mod, held = spans
    held += _moe_spans(mod)
    assert read(name, None) is None
    assert read(name, summary(ops={}, modules={})) is None


@pytest.mark.parametrize("name", ["gmm_roofline.moe",
                                  "decode_hbm_roofline.moe"])
def test_no_counter_reads_none(spans, name):
    mod, held = spans
    # a dense program: decode dispatches, no routing counter
    held.append(mod.Record("repro.serve.dispatch", 0, 1,
                           {"batch": 0, "token": 0, "ahead": True}, None))
    assert read(name, summary()) is None


@pytest.mark.parametrize("name", ["gmm_roofline.moe",
                                  "decode_hbm_roofline.moe"])
def test_a_program_without_spans_reads_none(monkeypatch, name):
    import repro.obs

    monkeypatch.delattr(repro.obs, "spans", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs.spans", None)
    assert read(name, summary()) is None


def test_gmm_roofline_without_the_kernel_reads_none(spans):
    mod, held = spans
    held += _moe_spans(mod)
    no_gmm = {k: v for k, v in summary().ops.items()
              if not any(f"%{n} " in k for n in ("gate", "up", "down", "one"))}
    assert read("gmm_roofline.moe", summary(ops=no_gmm)) is None
