"""The 10 assigned architecture configs (exact public-literature dims).

Sources per assignment brackets:
  granite-moe-*   [hf:ibm-granite/granite-3.0-1b-a400m-base]
  granite-20b     [arXiv:2405.04324]
  gemma3-4b       [hf:google/gemma-3-1b-pt family]
  deepseek-coder-33b [arXiv:2401.14196]
  codeqwen1.5-7b  [hf:Qwen/CodeQwen1.5-7B]
  jamba-v0.1-52b  [arXiv:2403.19887]
  whisper-base    [arXiv:2212.04356]
  paligemma-3b    [arXiv:2407.07726]
  falcon-mamba-7b [arXiv:2410.05355]
  granite-4.0-h-micro [hf:ibm-granite/granite-4.0-h-micro config.json]
"""

from __future__ import annotations

from ..models.config import ModelConfig

TP = 16  # model-axis size of the production mesh


def _attn_mode(n_heads: int) -> str:
    return "heads_tp" if n_heads % TP == 0 else "seq_tp"


GRANITE_MOE_1B = ModelConfig(
    name="granite-moe-1b-a400m", family="moe",
    n_layers=24, d_model=1024, n_heads=16, n_kv_heads=8,
    head_dim=64, d_ff=512, vocab_size=49155,
    n_experts=32, top_k=8, moe_period=1,
    act="silu", gated_mlp=True, attn_mode=_attn_mode(16),
)

GRANITE_MOE_3B = ModelConfig(
    name="granite-moe-3b-a800m", family="moe",
    n_layers=32, d_model=1536, n_heads=24, n_kv_heads=8,
    head_dim=64, d_ff=512, vocab_size=49155,
    n_experts=40, top_k=8, moe_period=1,
    act="silu", gated_mlp=True, attn_mode=_attn_mode(24),
)

GRANITE_20B = ModelConfig(
    name="granite-20b", family="dense",
    n_layers=52, d_model=6144, n_heads=48, n_kv_heads=1,
    head_dim=128, d_ff=24576, vocab_size=49152,
    act="gelu", gated_mlp=False,  # starcoder-style 4x GELU MLP, MQA
    attn_mode=_attn_mode(48),
)

GEMMA3_4B = ModelConfig(
    name="gemma3-4b", family="dense",
    n_layers=34, d_model=2560, n_heads=8, n_kv_heads=4,
    head_dim=256, d_ff=10240, vocab_size=262144,
    window=1024, local_global_period=6,  # 5 local : 1 global
    rope_theta=10_000.0, rope_theta_global=1_000_000.0,
    act="gelu", gated_mlp=True, rms_plus_one=True, embed_scale=True,
    attn_mode=_attn_mode(8),
)

DEEPSEEK_CODER_33B = ModelConfig(
    name="deepseek-coder-33b", family="dense",
    n_layers=62, d_model=7168, n_heads=56, n_kv_heads=8,
    head_dim=128, d_ff=19200, vocab_size=32256,
    act="silu", gated_mlp=True, attn_mode=_attn_mode(56),
)

CODEQWEN_7B = ModelConfig(
    name="codeqwen1.5-7b", family="dense",
    n_layers=32, d_model=4096, n_heads=32, n_kv_heads=32,
    head_dim=128, d_ff=13440, vocab_size=92416,
    act="silu", gated_mlp=True, attn_mode=_attn_mode(32),
)

JAMBA_52B = ModelConfig(
    name="jamba-v0.1-52b", family="hybrid",
    n_layers=32, d_model=4096, n_heads=32, n_kv_heads=8,
    head_dim=128, d_ff=14336, vocab_size=65536,
    n_experts=16, top_k=2, moe_period=2, moe_phase=1,
    attn_period=8, attn_phase=4,
    d_state=16, d_conv=4, expand=2,
    act="silu", gated_mlp=True, attn_mode=_attn_mode(32),
)

WHISPER_BASE = ModelConfig(
    name="whisper-base", family="encdec",
    n_layers=6, n_enc_layers=6, d_model=512, n_heads=8, n_kv_heads=8,
    head_dim=64, d_ff=2048, vocab_size=51865, dec_len=448,
    act="gelu", gated_mlp=False, norm="layernorm",
    attn_mode="seq_tp",
)

PALIGEMMA_3B = ModelConfig(
    name="paligemma-3b", family="vlm",
    n_layers=18, d_model=2048, n_heads=8, n_kv_heads=1,
    head_dim=256, d_ff=16384, vocab_size=257216,
    prefix_len=256,  # SigLIP patch prefix (stubbed embeddings)
    act="gelu", gated_mlp=True, rms_plus_one=True, embed_scale=True,
    attn_mode=_attn_mode(8),
)

FALCON_MAMBA_7B = ModelConfig(
    name="falcon-mamba-7b", family="ssm",
    n_layers=64, d_model=4096, n_heads=0, n_kv_heads=0,
    head_dim=0, d_ff=0, vocab_size=65024,
    d_state=16, d_conv=4, expand=2,
    act="silu", attn_mode="seq_tp",
)

# Mamba-2 mixers beside NoPE GQA attention at layers 5, 15, 25, 35
# (layer_types), a dense SwiGLU shared_mlp in every layer, no experts
GRANITE_4_H_MICRO = ModelConfig(
    name="granite-4.0-h-micro", family="hybrid",
    n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8,
    head_dim=64, d_ff=8192, vocab_size=100352,
    attn_period=10, attn_phase=5,
    d_state=128, d_conv=4, expand=2,
    ssm_version=2, ssm_heads=64, ssm_head_dim=64, ssm_groups=1, ssm_chunk=256,
    use_rope=False, attn_logit_scale=0.015625, norm_eps=1e-5,
    embed_multiplier=12.0, residual_multiplier=0.22, logits_divisor=8.0,
    act="silu", gated_mlp=True, tie_embeddings=True, attn_mode=_attn_mode(32),
)

ARCHS = {
    c.name: c
    for c in (
        GRANITE_MOE_1B, GRANITE_MOE_3B, GRANITE_20B, GEMMA3_4B,
        DEEPSEEK_CODER_33B, CODEQWEN_7B, JAMBA_52B, WHISPER_BASE,
        PALIGEMMA_3B, FALCON_MAMBA_7B, GRANITE_4_H_MICRO,
    )
}

# Sub-quadratic archs that run long_500k (others skip; see DESIGN.md).
LONG_CONTEXT_ARCHS = ("jamba-v0.1-52b", "falcon-mamba-7b", "gemma3-4b", "granite-4.0-h-micro")


def shape_supported(cfg: ModelConfig, shape_name: str) -> tuple[bool, str]:
    if shape_name == "long_500k" and cfg.name not in LONG_CONTEXT_ARCHS:
        return False, "pure full-attention arch: unbounded 500k KV on every layer (skip per assignment)"
    return True, ""


def reduced(cfg: ModelConfig) -> ModelConfig:
    """Tiny same-family config for CPU smoke tests."""
    kw = dict(
        n_layers=max(2, (4 if cfg.local_global_period == 0 else cfg.local_global_period)),
        d_model=64, d_ff=128, vocab_size=503,  # odd on purpose (exercises padding)
        q_chunk=16, kv_chunk=32, xent_chunk=64, remat=False,
    )
    if cfg.n_heads:
        kw.update(n_heads=4, n_kv_heads=max(1, min(cfg.n_kv_heads, 2)), head_dim=16)
        if cfg.n_kv_heads == cfg.n_heads:
            kw["n_kv_heads"] = 4
    if cfg.n_experts:
        kw.update(n_experts=4, top_k=2)
    if cfg.family == "hybrid" and cfg.ssm_version == 2:
        # a period of 4, attention at 2; 4 Mamba-2 heads of 16, chunks of 8
        kw.update(n_layers=4, attn_period=4, attn_phase=2, d_state=8, d_conv=4,
                  expand=1, ssm_heads=4, ssm_head_dim=16, ssm_chunk=8)
    elif cfg.family == "hybrid":
        kw.update(n_layers=8, d_state=4, d_conv=4)
    if cfg.family == "ssm":
        kw.update(n_layers=2, d_state=4, d_conv=4)
    if cfg.family == "encdec":
        kw.update(n_layers=2, n_enc_layers=2, dec_len=32)
    if cfg.family == "vlm":
        kw.update(prefix_len=8)
    if cfg.local_global_period:
        kw.update(n_layers=8, window=16)  # 1 super-block of 6 + tail of 2
    return cfg.replace(**kw)
