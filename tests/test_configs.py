"""Config registry: every assigned architecture loads with exact dims."""
import pytest

from repro.configs.base import get_arch, list_archs, smoke_config

ASSIGNED = {
    "granite-20b": dict(n_layers=52, d_model=6144, n_heads=48, n_kv_heads=1,
                        d_ff=24576, vocab_size=49152),
    "qwen3-0.6b": dict(n_layers=28, d_model=1024, n_heads=16, n_kv_heads=8,
                       d_ff=3072, vocab_size=151936, qk_norm=True),
    "starcoder2-3b": dict(n_layers=30, d_model=3072, n_heads=24,
                          n_kv_heads=2, d_ff=12288, vocab_size=49152),
    "gemma3-4b": dict(n_layers=34, d_model=2560, n_heads=8, n_kv_heads=4,
                      d_ff=10240, vocab_size=262144),
    "seamless-m4t-large-v2": dict(n_layers=24, d_model=1024, n_heads=16,
                                  n_kv_heads=16, d_ff=8192,
                                  vocab_size=256206, enc_dec=True,
                                  n_enc_layers=24),
    "recurrentgemma-9b": dict(n_layers=38, d_model=4096, n_heads=16,
                              n_kv_heads=1, d_ff=12288, vocab_size=256000),
    "rwkv6-7b": dict(n_layers=32, d_model=4096, d_ff=14336,
                     vocab_size=65536),
    "llama4-scout-17b-a16e": dict(n_layers=48, d_model=5120, n_heads=40,
                                  n_kv_heads=8, d_ff=8192,
                                  vocab_size=202048),
    "mixtral-8x22b": dict(n_layers=56, d_model=6144, n_heads=48,
                          n_kv_heads=8, d_ff=16384, vocab_size=32768),
    "llava-next-34b": dict(n_layers=60, d_model=7168, n_heads=56,
                           n_kv_heads=8, d_ff=20480, vocab_size=64000),
    "mellum2-12b": dict(n_layers=28, d_model=2304, n_heads=32, n_kv_heads=4,
                        d_head=128, d_ff=896, vocab_size=98304, window=1024,
                        norm_eps=1e-6, rope_theta=500_000.0),
}


def test_all_assigned_archs_registered():
    archs = set(list_archs())
    assert set(ASSIGNED) <= archs


@pytest.mark.parametrize("name", sorted(ASSIGNED))
def test_exact_dims(name):
    cfg = get_arch(name)
    for field, expect in ASSIGNED[name].items():
        assert getattr(cfg, field) == expect, (name, field)


@pytest.mark.parametrize("name", sorted(ASSIGNED))
def test_block_groups_cover_layers(name):
    cfg = get_arch(name)
    assert len(cfg.layer_kinds()) == cfg.n_layers


def test_moe_specs():
    mix = get_arch("mixtral-8x22b")
    assert mix.moe.n_experts == 8 and mix.moe.top_k == 2
    l4 = get_arch("llama4-scout-17b-a16e")
    assert l4.moe.n_experts == 16 and l4.moe.top_k == 1 and l4.moe.shared_expert
    mel = get_arch("mellum2-12b")
    assert mel.moe.n_experts == 64 and mel.moe.top_k == 8
    assert mel.moe.dropless and not mel.moe.shared_expert
    assert mel.layer_kinds() == ["local", "local", "local", "global"] * 7
    assert mel.yarn.factor == 16 and mel.yarn.original_max_position == 8192


def test_param_counts_plausible():
    # headline parameter counts within tolerance of the public numbers
    approx = {
        "granite-20b": (20e9, 0.3),
        "gemma3-4b": (4.3e9, 0.35),
        "rwkv6-7b": (7.6e9, 0.35),
        "mixtral-8x22b": (141e9, 0.2),
        "llava-next-34b": (34e9, 0.25),
        "mellum2-12b": (12e9, 0.05),
    }
    for name, (target, tol) in approx.items():
        n = get_arch(name).param_count()
        assert abs(n - target) / target < tol, (name, n)


def test_moe_active_params_less_than_total():
    for name in ("mixtral-8x22b", "llama4-scout-17b-a16e"):
        cfg = get_arch(name)
        assert cfg.active_param_count() < 0.55 * cfg.param_count()
    # Mellum2-12B-A2.5B: 2.5 B of its 12 B active a token
    assert abs(get_arch("mellum2-12b").active_param_count() - 2.5e9) < 0.1e9




@pytest.mark.parametrize("name", sorted(ASSIGNED))
def test_smoke_config_valid(name):
    s = smoke_config(get_arch(name))
    assert s.n_layers == len(s.layer_kinds())
    assert s.vocab_size <= 1024 and s.d_model <= 128
