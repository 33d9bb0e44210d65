"""Hybrid decoders: Mamba mixers with one attention layer per period.

- Jamba-v0.1 (arXiv:2403.19887): 4 "Jamba blocks" of 8 layers; layer 4 of a
  block is attention, the rest Mamba-1 mixers; odd layers carry MoE FFNs,
  even layers dense FFNs.
- Granite-4.0-H (HF ``granitemoehybrid``): periods of 10 layers, attention
  without position encoding at 5, Mamba-2 mixers (``mamba2.py``) elsewhere,
  a dense SwiGLU MLP in every layer, and the embedding, residual and logits
  multipliers.

The period is ``cfg.attn_period`` layers.  We scan over the homogeneous
periods (params stacked [n_periods, ...] per position), so the HLO contains
one unrolled period.  Under ``cfg.remat`` a Mamba-1 hybrid (Jamba)
rematerialises each whole period, a Mamba-2 hybrid each layer on its own
(``_maybe_remat``), so that its backward holds one layer's activations.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..parallel.spec import ParamSpec, logical_constraint as lc
from . import mamba2
from .common import chunked_cross_entropy, rms_norm
from .config import ModelConfig
from .mamba import init_state_specs as _mamba_state_specs  # noqa: F401
from .mamba import mamba_layer_specs, mamba_mixer
from .transformer import (
    LOCAL_CTX,
    ShardCtx,
    _attention_block,
    _attn_specs,
    _decode_layer,
    _embed,
    _ffn_or_moe,
    _ffn_specs,
    _maybe_remat,
    _moe_specs,
    _unembed_weight,
    residual,
)


def _n_periods(cfg: ModelConfig) -> int:
    if cfg.attn_period <= 0 or cfg.n_layers % cfg.attn_period:
        raise ValueError(f"{cfg.name}: {cfg.n_layers} layers are not whole "
                         f"periods of {cfg.attn_period}")
    return cfg.n_layers // cfg.attn_period


def _pos_specs(cfg: ModelConfig, j: int, n_super: int) -> Dict[str, Any]:
    """Specs for position j within the period, stacked over n_super."""
    D = cfg.d_model
    s: Dict[str, Any] = {}
    if j == cfg.attn_phase:
        s["ln1"] = ParamSpec((n_super, D), ("layers", "embed"), jnp.float32, init="ones")
        s["attn"] = _attn_specs(cfg, n_super)
    elif cfg.ssm_version == 2:
        s["mixer"] = mamba2.layer_specs(cfg, n_super)
    else:
        s["mixer"] = mamba_layer_specs(cfg, n_super)
    s["ln2"] = ParamSpec((n_super, D), ("layers", "embed"), jnp.float32, init="ones")
    if cfg.is_moe_layer(j):
        s["moe"] = _moe_specs(cfg, n_super)
    else:
        s["ffn"] = _ffn_specs(cfg, n_super)
    return s


def param_specs(cfg: ModelConfig) -> Dict[str, Any]:
    n_super = _n_periods(cfg)
    D, Vp = cfg.d_model, cfg.vocab_padded
    return {
        "embed": ParamSpec((Vp, D), ("vocab", "embed"), cfg.dtype),
        "final_norm": ParamSpec((D,), ("embed",), jnp.float32, init="ones"),
        "blocks": {
            f"pos{j}": _pos_specs(cfg, j, n_super) for j in range(cfg.attn_period)
        },
    }


def _hybrid_layer(cfg: ModelConfig, j: int, ctx: ShardCtx, lp, x):
    """Layer j of a period, full sequence."""
    if j == cfg.attn_phase:
        with jax.named_scope("repro.attention"):
            x = _attention_block(cfg, lp, x, layer_global=True, prefix=None, ctx=ctx)
    else:
        h = rms_norm(x, lp["mixer"]["ln"], eps=cfg.norm_eps)
        mix = mamba2.mixer if cfg.ssm_version == 2 else mamba_mixer
        x = x + residual(cfg, mix(cfg, lp["mixer"], h, ctx))
    with jax.named_scope("repro.mlp"):
        return _ffn_or_moe(cfg, lp, x, cfg.is_moe_layer(j), ctx)


def _super_block(cfg: ModelConfig, lps, x, ctx: ShardCtx):
    for j in range(cfg.attn_period):
        layer = functools.partial(_hybrid_layer, cfg, j, ctx)
        if cfg.ssm_version == 2:
            layer = _maybe_remat(cfg, layer)
        x = layer(lps[f"pos{j}"], x)
    return x


def _backbone(cfg: ModelConfig, params, x, ctx: ShardCtx, train: bool = False):
    def body(x, lps):
        return _super_block(cfg, lps, x, ctx), None

    if train and cfg.remat and cfg.ssm_version != 2:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["blocks"], unroll=True if cfg.unroll_scans else 1)
    return _head_input(cfg, rms_norm(x, params["final_norm"], eps=cfg.norm_eps))


def _head_input(cfg: ModelConfig, x):
    """The final hidden state as the tied head reads it: divided by the
    logits divisor, so that the logits are divided by it."""
    if cfg.logits_divisor == 1.0:
        return x
    return (x.astype(jnp.float32) / cfg.logits_divisor).astype(x.dtype)


def loss_fn(cfg: ModelConfig, params, batch, ctx: ShardCtx = LOCAL_CTX):
    x = _backbone(cfg, params, _embed(cfg, params, batch["tokens"], ctx), ctx, train=True)
    B, S, D = x.shape
    return chunked_cross_entropy(
        x.reshape(B * S, D), _unembed_weight(cfg, params),
        batch["labels"].reshape(B * S), chunk=min(cfg.xent_chunk, B * S),
        rules=ctx.rules, unroll=cfg.unroll_scans,
    )


def init_cache_specs(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Any]:
    """Per-position caches: KV for the attention position, SSM+conv states
    for mamba positions."""
    n_super = _n_periods(cfg)
    KV, hd = cfg.n_kv_heads, cfg.head_dim
    di, ds, dc = cfg.d_inner, cfg.d_state, cfg.d_conv
    caches: Dict[str, Any] = {}
    for j in range(cfg.attn_period):
        if j == cfg.attn_phase:
            caches[f"pos{j}"] = {
                "k": ParamSpec((n_super, batch, max_len, KV, hd),
                               ("layers", "batch", "kv_seq", "kv_heads", None), cfg.dtype, init="zeros"),
                "v": ParamSpec((n_super, batch, max_len, KV, hd),
                               ("layers", "batch", "kv_seq", "kv_heads", None), cfg.dtype, init="zeros"),
            }
        elif cfg.ssm_version == 2:
            caches[f"pos{j}"] = mamba2.state_specs(cfg, n_super, batch)
        else:
            caches[f"pos{j}"] = {
                "h": ParamSpec((n_super, batch, di, ds), ("layers", "batch", "mlp", "state"), jnp.float32, init="zeros"),
                "conv": ParamSpec((n_super, batch, dc - 1, di), ("layers", "batch", None, "mlp"), cfg.dtype, init="zeros"),
            }
    return {"blocks": caches}


def decode_step(cfg: ModelConfig, params, cache, token, pos, ctx: ShardCtx = LOCAL_CTX):
    x = _embed(cfg, params, token, ctx)

    def body(x, lps_caches):
        lps, cch = lps_caches
        new_c: Dict[str, Any] = {}
        for j in range(cfg.attn_period):
            lp, c = lps[f"pos{j}"], cch[f"pos{j}"]
            is_moe = cfg.is_moe_layer(j)
            if j == cfg.attn_phase:
                x, new_c[f"pos{j}"] = _decode_layer(
                    cfg, lp, c, x, pos, layer_global=True, is_moe=is_moe, ctx=ctx)
                continue
            xn = rms_norm(x, lp["mixer"]["ln"], eps=cfg.norm_eps)
            if cfg.ssm_version == 2:
                out, new_c[f"pos{j}"] = mamba2.mixer(cfg, lp["mixer"], xn, ctx, state=c)
            else:
                out, (h2, conv2) = mamba_mixer(cfg, lp["mixer"], xn, ctx,
                                               h0=c["h"], conv_state=c["conv"])
                new_c[f"pos{j}"] = {"h": h2, "conv": conv2}
            x = x + residual(cfg, out)
            x = _ffn_or_moe(cfg, lp, x, is_moe, ctx)
        return x, new_c

    x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache["blocks"]),
                                unroll=True if cfg.unroll_scans else 1)
    x = _head_input(cfg, rms_norm(x, params["final_norm"], eps=cfg.norm_eps))
    logits = jnp.einsum("bsd,dv->bsv", x, _unembed_weight(cfg, params))
    logits = lc(logits, ("batch", None, "vocab"), ctx.rules)
    return logits[:, 0], {"blocks": new_cache}


def prefill(cfg: ModelConfig, params, tokens, ctx: ShardCtx = LOCAL_CTX):
    x = _backbone(cfg, params, _embed(cfg, params, tokens, ctx), ctx)
    logits = jnp.einsum("bd,dv->bv", x[:, -1], _unembed_weight(cfg, params))
    return lc(logits, ("batch", "vocab"), ctx.rules)
