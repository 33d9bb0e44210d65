"""One config dataclass for every architecture that ``configs/archs.py`` registers."""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax.numpy as jnp

__all__ = ["ModelConfig"]


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | hybrid | ssm | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int

    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    moe_period: int = 1  # layer i is MoE iff family has moe and i % moe_period == moe_phase
    moe_phase: int = 0
    capacity_factor: float = 1.25
    router_renormalize: bool = True

    # --- attention pattern ---
    gated_mlp: bool = True  # SwiGLU/GeGLU vs plain MLP
    window: Optional[int] = None  # sliding-window size for "local" layers
    local_global_period: int = 0  # gemma3: 6 -> every 6th layer is global
    rope_theta: float = 10_000.0
    rope_theta_global: float = 1_000_000.0
    attn_logit_scale: Optional[float] = None  # override 1/sqrt(head_dim)

    # --- hybrid (jamba, granite-4.0-h) ---
    attn_period: int = 0  # jamba: 8 -> one attention layer per 8
    attn_phase: int = 4

    # --- ssm (mamba1, mamba2) ---
    d_state: int = 0
    d_conv: int = 4
    expand: int = 2
    ssm_version: int = 1  # 1: Mamba-1 selective scan; 2: Mamba-2 (SSD)
    ssm_heads: int = 0  # Mamba-2: heads of ssm_head_dim channels, heads * head_dim = d_inner
    ssm_head_dim: int = 64
    ssm_groups: int = 1  # Mamba-2: B/C groups shared by heads
    ssm_chunk: int = 256  # Mamba-2: SSD chunk length

    # --- enc-dec (whisper) ---
    n_enc_layers: int = 0
    dec_len: int = 448

    # --- vlm (paligemma) ---
    prefix_len: int = 0  # image-patch prefix (stub embeddings)

    # --- misc ---
    act: str = "silu"
    norm: str = "rmsnorm"
    rms_plus_one: bool = False  # gemma convention
    embed_scale: bool = False  # gemma: x *= sqrt(d_model)
    tie_embeddings: bool = True
    dtype: Any = jnp.bfloat16
    norm_eps: float = 1e-6
    use_rope: bool = True  # False: no position encoding (NoPE)
    # granite multipliers: x0 = m_e * embed; x += m_r * branch; logits / d
    embed_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_divisor: float = 1.0

    # --- runtime / partitioning ---
    attn_mode: str = "heads_tp"  # heads_tp | seq_tp
    q_chunk: int = 256
    kv_chunk: int = 1024
    xent_chunk: int = 2048
    ssm_scan_chunk: int = 64
    remat: bool = True

    # ------------------------------------------------------------------
    @property
    def vocab_padded(self) -> int:
        return _round_up(self.vocab_size, 256)

    @property
    def n_experts_padded(self) -> int:
        """Experts padded so EP over 16 divides evenly (dummy experts get no
        traffic: router logits only span the real experts)."""
        if self.n_experts == 0:
            return 0
        return _round_up(self.n_experts, 16)

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def ssm_conv_dim(self) -> int:
        """Mamba-2: channels through the conv (x, B and C)."""
        return self.d_inner + 2 * self.ssm_groups * self.d_state

    @property
    def dt_rank(self) -> int:
        return max(1, (self.d_model + 15) // 16)

    def is_moe_layer(self, i: int) -> bool:
        if self.n_experts == 0:
            return False
        return i % self.moe_period == self.moe_phase

    def is_attn_layer(self, i: int) -> bool:
        if self.family != "hybrid":
            return self.family != "ssm"
        return self.attn_period > 0 and i % self.attn_period == self.attn_phase

    def is_global_layer(self, i: int) -> bool:
        """gemma3 5:1 pattern — every ``local_global_period``-th layer global."""
        if self.local_global_period == 0:
            return True  # no local/global distinction -> all global
        return i % self.local_global_period == self.local_global_period - 1

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # approximate parameter counts (for 6ND roofline bookkeeping)
    def param_count(self) -> int:
        D, H, KV, hd, F, L = (
            self.d_model, self.n_heads, self.n_kv_heads, self.head_dim, self.d_ff, self.n_layers,
        )
        total = self.vocab_padded * D  # embed (tied)
        if not self.tie_embeddings:
            total += self.vocab_padded * D
        for i in range(L):
            total += 2 * D  # norms
            if self.is_attn_layer(i):
                total += D * (H + 2 * KV) * hd + H * hd * D
            elif self.family in ("ssm", "hybrid") and self.ssm_version == 2:
                di, hs, cd = self.d_inner, self.ssm_heads, self.ssm_conv_dim
                total += D * (di + cd + hs) + cd * (self.d_conv + 1) + 3 * hs + di + di * D
            elif self.family in ("ssm", "hybrid"):
                di, ds, dr = self.d_inner, self.d_state, self.dt_rank
                total += D * 2 * di + di * self.d_conv + di * (dr + 2 * ds) + dr * di + di + di * ds + di * D
            if self.is_moe_layer(i):
                E = self.n_experts
                gates = 3 if self.gated_mlp else 2
                total += D * E + E * gates * D * F
            elif self.family != "ssm" or not self.is_attn_layer(i):
                gates = 3 if self.gated_mlp else 2
                if self.family != "ssm":
                    total += gates * D * F
        if self.family == "encdec":
            # encoder layers + cross-attention in decoder
            for _ in range(self.n_enc_layers):
                total += 2 * D + D * (H + 2 * KV) * hd + H * hd * D
                total += (3 if self.gated_mlp else 2) * D * F
            total += self.n_layers * (D * (H + 2 * KV) * hd + H * hd * D + D)
        return total

    def active_param_count(self) -> int:
        """MoE: only top_k of n_experts active per token."""
        if self.n_experts == 0:
            return self.param_count()
        total = self.param_count()
        E, K = self.n_experts, self.top_k
        gates = 3 if self.gated_mlp else 2
        n_moe = sum(1 for i in range(self.n_layers) if self.is_moe_layer(i))
        expert_params = n_moe * E * gates * self.d_model * self.d_ff
        active = n_moe * K * gates * self.d_model * self.d_ff
        return total - expert_params + active
