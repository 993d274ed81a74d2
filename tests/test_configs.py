"""Config system: every arch resolves, exact assigned dims, smoke contract."""

import pytest

from repro.config import (ARCH_IDS, CLASSIC_IDS, INPUT_SHAPES, get_config,
                          get_smoke_config)

EXPECTED_DIMS = {
    # arch: (layers, d_model, heads, kv_heads, d_ff, vocab)
    "mamba2-370m": (48, 1024, 0, 0, 0, 50280),
    "deepseek-moe-16b": (28, 2048, 16, 16, 1408, 102400),
    "minicpm-2b": (40, 2304, 36, 36, 5760, 122753),
    "qwen2.5-14b": (48, 5120, 40, 8, 13824, 152064),
    "musicgen-medium": (48, 1536, 24, 24, 6144, 2048),
    "jamba-1.5-large-398b": (72, 8192, 64, 8, 24576, 65536),
    "paligemma-3b": (18, 2048, 8, 1, 16384, 257216),
    "deepseek-coder-33b": (62, 7168, 56, 8, 19200, 32256),
    "olmoe-1b-7b": (16, 2048, 16, 16, 1024, 50304),
    "qwen3-1.7b": (28, 2048, 16, 8, 6144, 151936),
    "granite-4.0-h-micro": (40, 2048, 32, 8, 8192, 100352),
}

EXPECTED_MOE = {
    "deepseek-moe-16b": (64, 6, 2),      # experts, top_k, shared
    "jamba-1.5-large-398b": (16, 2, 0),
    "olmoe-1b-7b": (64, 8, 0),
}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_exact_assigned_dims(arch):
    m = get_config(arch).model
    exp = EXPECTED_DIMS[arch]
    assert (m.n_layers, m.d_model, m.n_heads, m.n_kv_heads, m.d_ff,
            m.vocab_size) == exp
    assert m.source, "every config must cite its source"


@pytest.mark.parametrize("arch", list(EXPECTED_MOE))
def test_moe_dims(arch):
    m = get_config(arch).model.moe
    assert (m.num_experts, m.top_k, m.num_shared_experts) == \
        EXPECTED_MOE[arch]


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_contract(arch):
    """Reduced variant: <=2 layers, d_model<=512, <=4 experts."""
    m = get_smoke_config(arch).model
    assert m.n_layers <= 2
    assert m.d_model <= 512
    assert m.moe.num_experts <= 4
    full = get_config(arch).model
    assert m.family == full.family
    # family-defining flags preserved
    assert m.qk_norm == full.qk_norm
    assert m.qkv_bias == full.qkv_bias
    assert (m.moe.enabled) == (full.moe.enabled)
    assert m.n_codebooks == full.n_codebooks
    assert (m.num_prefix_embeddings > 0) == (full.num_prefix_embeddings > 0)


def test_param_counts_match_model_names():
    """Analytic param counts land near the advertised sizes."""
    expect_b = {
        "mamba2-370m": 0.37, "deepseek-moe-16b": 16.3, "minicpm-2b": 2.7,
        "qwen2.5-14b": 14.8, "jamba-1.5-large-398b": 398.0,
        "deepseek-coder-33b": 33.3, "olmoe-1b-7b": 6.9, "qwen3-1.7b": 1.7,
    }
    for arch, b in expect_b.items():
        n = get_config(arch).model.num_params() / 1e9
        assert abs(n - b) / b < 0.15, (arch, n, b)


def test_active_params_moe():
    cfg = get_config("olmoe-1b-7b").model
    # OLMoE: ~6.9B total, ~1.3B active
    assert cfg.num_active_params() < 0.25 * cfg.num_params()


def test_input_shapes_assigned():
    assert INPUT_SHAPES["train_4k"].seq_len == 4096
    assert INPUT_SHAPES["train_4k"].global_batch == 256
    assert INPUT_SHAPES["prefill_32k"].seq_len == 32768
    assert INPUT_SHAPES["prefill_32k"].global_batch == 32
    assert INPUT_SHAPES["decode_32k"].global_batch == 128
    assert INPUT_SHAPES["long_500k"].seq_len == 524288
    assert INPUT_SHAPES["long_500k"].global_batch == 1
    assert INPUT_SHAPES["decode_32k"].kind == "decode"
    assert INPUT_SHAPES["long_500k"].kind == "decode"


def test_unknown_arch_raises():
    with pytest.raises(KeyError):
        get_config("nope-7b")


@pytest.mark.parametrize("arch", CLASSIC_IDS)
def test_classic_configs(arch):
    cfg = get_config(arch)
    assert cfg.model.family == "classic"
