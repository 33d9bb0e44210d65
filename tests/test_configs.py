"""Architecture config sanity: exact assigned dims, padding rules, cells."""

import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES, get_config, list_cells, reduced, shape_supported


EXPECTED_DIMS = {
    # (layers, d_model, heads, kv, d_ff, vocab)
    "granite-moe-1b-a400m": (24, 1024, 16, 8, 512, 49155),
    "granite-moe-3b-a800m": (32, 1536, 24, 8, 512, 49155),
    "granite-20b": (52, 6144, 48, 1, 24576, 49152),
    "gemma3-4b": (34, 2560, 8, 4, 10240, 262144),
    "deepseek-coder-33b": (62, 7168, 56, 8, 19200, 32256),
    "codeqwen1.5-7b": (32, 4096, 32, 32, 13440, 92416),
    "jamba-v0.1-52b": (32, 4096, 32, 8, 14336, 65536),
    "whisper-base": (6, 512, 8, 8, 2048, 51865),
    "paligemma-3b": (18, 2048, 8, 1, 16384, 257216),
    "falcon-mamba-7b": (64, 4096, 0, 0, 0, 65024),
    "granite-4.0-h-micro": (40, 2048, 32, 8, 8192, 100352),
}


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_assigned_dims_exact(name):
    c = get_config(name)
    exp = EXPECTED_DIMS[name]
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff, c.vocab_size) == exp


def test_moe_configs():
    c1 = get_config("granite-moe-1b-a400m")
    assert (c1.n_experts, c1.top_k) == (32, 8) and c1.n_experts_padded == 32
    c3 = get_config("granite-moe-3b-a800m")
    assert (c3.n_experts, c3.top_k) == (40, 8) and c3.n_experts_padded == 48
    j = get_config("jamba-v0.1-52b")
    assert (j.n_experts, j.top_k, j.attn_period) == (16, 2, 8)


def test_vocab_padding_multiple_of_256():
    for c in ARCHS.values():
        assert c.vocab_padded % 256 == 0 and c.vocab_padded >= c.vocab_size


def test_param_counts_plausible():
    # ballpark totals (within 35% of the named sizes; vocab+arch variants)
    approx = {
        "granite-20b": 20e9, "deepseek-coder-33b": 33e9, "codeqwen1.5-7b": 7e9,
        "falcon-mamba-7b": 7e9, "jamba-v0.1-52b": 52e9,
    }
    for name, target in approx.items():
        n = get_config(name).param_count()
        assert 0.65 * target < n < 1.35 * target, (name, n)
    # MoE active < total
    gm = get_config("granite-moe-1b-a400m")
    assert gm.active_param_count() < gm.param_count()


def test_40_cells_accounted():
    cells = list_cells()
    assert len(cells) == 44  # 11 archs x 4 shapes
    skips = [c for c in cells if not c["run"]]
    assert len(skips) == 7  # 7 archs skip long_500k
    assert all(c["shape"] == "long_500k" for c in skips)


def test_gemma3_local_global_pattern():
    c = get_config("gemma3-4b")
    globals_ = [i for i in range(c.n_layers) if c.is_global_layer(i)]
    assert globals_ == [5, 11, 17, 23, 29]  # every 6th of 34 layers


def test_granite_h_micro_published_sizes():
    c = get_config("granite-4.0-h-micro")
    assert [i for i in range(c.n_layers) if c.is_attn_layer(i)] == [5, 15, 25, 35]
    assert (c.ssm_version, c.ssm_heads, c.ssm_head_dim, c.ssm_groups, c.d_state,
            c.d_conv, c.expand, c.ssm_chunk) == (2, 64, 64, 1, 128, 4, 2, 256)
    assert c.ssm_conv_dim == 4352 and c.vocab_padded == c.vocab_size
    assert (c.use_rope, c.attn_logit_scale, c.norm_eps) == (False, 1 / 64, 1e-5)
    assert (c.embed_multiplier, c.residual_multiplier, c.logits_divisor) == (12, 0.22, 8)
    assert c.n_experts == 0 and not any(c.is_moe_layer(i) for i in range(c.n_layers))
    # 3.19 B parameters at 40 layers (ibm-granite/granite-4.0-h-micro)
    assert abs(c.param_count() - 3.19e9) < 0.005 * 3.19e9
    assert abs(c.replace(n_layers=10).param_count() - 951.7e6) < 1e6


def test_jamba_attn_positions():
    c = get_config("jamba-v0.1-52b")
    attn = [i for i in range(c.n_layers) if c.is_attn_layer(i)]
    assert attn == [4, 12, 20, 28]  # 1 per 8-layer block
    moe = [i for i in range(c.n_layers) if c.is_moe_layer(i)]
    assert len(moe) == 16  # every other layer


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_reduced_configs_are_small(name):
    c = reduced(get_config(name))
    assert c.d_model <= 64 and c.n_layers <= 8
    assert c.param_count() < 10_000_000


def test_shapes_registry():
    assert SHAPES["train_4k"].kind == "train"
    assert SHAPES["decode_32k"].kind == "decode"
    assert SHAPES["long_500k"].global_batch == 1
