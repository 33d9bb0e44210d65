"""Whisper-base encoder-decoder (arXiv:2212.04356), transformer backbone only.

The conv frontend is a STUB per the assignment: ``input_specs()`` provides
precomputed frame embeddings [B, S_enc, D] (the two stride-2 convs of real
Whisper happen upstream). Sinusoidal positions, pre-LayerNorm blocks, GELU
MLPs, MHA (kv == heads). Decoder: causal self-attention + cross-attention
over encoder states.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.spec import ParamSpec, logical_constraint as lc
from .common import attention_decode, attention_seq_tp, chunked_cross_entropy, layer_norm
from .config import ModelConfig
from .transformer import LOCAL_CTX, ShardCtx


def _attn(cfg: ModelConfig, L: int) -> Dict[str, ParamSpec]:
    D, H, hd = cfg.d_model, cfg.n_heads, cfg.head_dim
    return {
        "wq": ParamSpec((L, D, H, hd), ("layers", "embed", "heads", None), cfg.dtype),
        "wk": ParamSpec((L, D, H, hd), ("layers", "embed", "kv_heads", None), cfg.dtype),
        "wv": ParamSpec((L, D, H, hd), ("layers", "embed", "kv_heads", None), cfg.dtype),
        "wo": ParamSpec((L, H, hd, D), ("layers", "heads", None, "embed"), cfg.dtype),
    }


def _ln(L: int, D: int, what: str) -> Dict[str, ParamSpec]:
    return {
        f"{what}_scale": ParamSpec((L, D), ("layers", "embed"), jnp.float32, init="ones"),
        f"{what}_bias": ParamSpec((L, D), ("layers", "embed"), jnp.float32, init="zeros"),
    }


def _mlp(cfg: ModelConfig, L: int) -> Dict[str, ParamSpec]:
    D, F = cfg.d_model, cfg.d_ff
    return {
        "w_in": ParamSpec((L, D, F), ("layers", "embed", "mlp"), cfg.dtype),
        "w_out": ParamSpec((L, F, D), ("layers", "mlp", "embed"), cfg.dtype),
    }


def param_specs(cfg: ModelConfig) -> Dict[str, Any]:
    D, Vp = cfg.d_model, cfg.vocab_padded
    Le, Ld = cfg.n_enc_layers, cfg.n_layers
    return {
        "embed": ParamSpec((Vp, D), ("vocab", "embed"), cfg.dtype),
        "enc": {
            **_ln(Le, D, "ln1"), **_ln(Le, D, "ln2"),
            "attn": _attn(cfg, Le), "mlp": _mlp(cfg, Le),
        },
        "enc_final": {
            "scale": ParamSpec((D,), ("embed",), jnp.float32, init="ones"),
            "bias": ParamSpec((D,), ("embed",), jnp.float32, init="zeros"),
        },
        "dec": {
            **_ln(Ld, D, "ln1"), **_ln(Ld, D, "ln2"), **_ln(Ld, D, "ln3"),
            "self_attn": _attn(cfg, Ld),
            "cross_attn": _attn(cfg, Ld),
            "mlp": _mlp(cfg, Ld),
        },
        "dec_final": {
            "scale": ParamSpec((D,), ("embed",), jnp.float32, init="ones"),
            "bias": ParamSpec((D,), ("embed",), jnp.float32, init="zeros"),
        },
    }


def _sinusoid(S: int, D: int, dtype) -> jnp.ndarray:
    pos = np.arange(S)[:, None]
    dim = np.arange(D // 2)[None, :]
    inv = np.exp(-np.log(10000.0) * dim / max(D // 2 - 1, 1))
    tab = np.concatenate([np.sin(pos * inv), np.cos(pos * inv)], axis=1)
    return jnp.asarray(tab, dtype)


def _self_attention(cfg, lp, x, causal, ctx, name="attn", kv_x=None):
    h = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dhk->bshk", x, lp[name]["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, lp[name]["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, lp[name]["wv"])
    k = lc(k, ("batch", None, "kv_heads", None), ctx.rules)
    v = lc(v, ("batch", None, "kv_heads", None), ctx.rules)
    o = attention_seq_tp(q, k, v, causal=causal, kv_chunk=cfg.kv_chunk, rules=ctx.rules)
    return jnp.einsum("bshk,hkd->bsd", o, lp[name]["wo"])


def _enc_layer(cfg, lp, x, ctx):
    h = layer_norm(x, lp["ln1_scale"], lp["ln1_bias"])
    x = x + _self_attention(cfg, lp, h, causal=False, ctx=ctx)
    h = layer_norm(x, lp["ln2_scale"], lp["ln2_bias"])
    h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", h, lp["mlp"]["w_in"]))
    h = lc(h, ("batch", "act_seq", "mlp"), ctx.rules)
    return x + jnp.einsum("bsf,fd->bsd", h, lp["mlp"]["w_out"])


def encode(cfg: ModelConfig, params, frames, ctx: ShardCtx = LOCAL_CTX):
    """frames: [B, S_enc, D] stub embeddings -> encoder states."""
    B, S, D = frames.shape
    x = frames.astype(cfg.dtype) + _sinusoid(S, D, cfg.dtype)
    x = lc(x, ("batch", "act_seq", "embed"), ctx.rules)

    def body(x, lp):
        return _enc_layer(cfg, lp, x, ctx), None

    body = jax.checkpoint(body) if cfg.remat else body
    x, _ = jax.lax.scan(body, x, params["enc"])
    return layer_norm(x, params["enc_final"]["scale"], params["enc_final"]["bias"])


def _dec_layer(cfg, lp, x, enc_states, ctx):
    h = layer_norm(x, lp["ln1_scale"], lp["ln1_bias"])
    x = x + _self_attention(cfg, lp, h, causal=True, ctx=ctx, name="self_attn")
    h = layer_norm(x, lp["ln2_scale"], lp["ln2_bias"])
    x = x + _self_attention(cfg, lp, h, causal=False, ctx=ctx, name="cross_attn",
                            kv_x=enc_states)
    h = layer_norm(x, lp["ln3_scale"], lp["ln3_bias"])
    h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", h, lp["mlp"]["w_in"]))
    h = lc(h, ("batch", "act_seq", "mlp"), ctx.rules)
    return x + jnp.einsum("bsf,fd->bsd", h, lp["mlp"]["w_out"])


def decode_train(cfg: ModelConfig, params, tokens, enc_states, ctx: ShardCtx = LOCAL_CTX):
    B, S = tokens.shape
    x = params["embed"].astype(cfg.dtype)[tokens] + _sinusoid(S, cfg.d_model, cfg.dtype)
    x = lc(x, ("batch", "act_seq", "embed"), ctx.rules)

    def body(x, lp):
        return _dec_layer(cfg, lp, x, enc_states, ctx), None

    body = jax.checkpoint(body) if cfg.remat else body
    x, _ = jax.lax.scan(body, x, params["dec"])
    return layer_norm(x, params["dec_final"]["scale"], params["dec_final"]["bias"])


def loss_fn(cfg: ModelConfig, params, batch, ctx: ShardCtx = LOCAL_CTX):
    """batch: {"frames": [B, S_enc, D], "tokens": [B, S_dec], "labels": ...}."""
    enc_states = encode(cfg, params, batch["frames"], ctx)
    x = decode_train(cfg, params, batch["tokens"], enc_states, ctx)
    B, S, D = x.shape
    return chunked_cross_entropy(
        x.reshape(B * S, D), params["embed"].astype(cfg.dtype).T,
        batch["labels"].reshape(B * S), chunk=min(cfg.xent_chunk, B * S),
        rules=ctx.rules,
    )


def prefill_logits(cfg: ModelConfig, params, frames, ctx: ShardCtx = LOCAL_CTX):
    """Inference-prefill: encode the full frame sequence (the dominant cost)
    and produce first-token logits from a BOS-only decoder pass."""
    enc_states = encode(cfg, params, frames, ctx)
    B = frames.shape[0]
    tokens = jnp.zeros((B, 1), jnp.int32)
    x = decode_train(cfg, params, tokens, enc_states, ctx)
    logits = jnp.einsum("bd,dv->bv", x[:, -1], params["embed"].astype(cfg.dtype).T)
    return lc(logits, ("batch", "vocab"), ctx.rules)


def init_cache_specs(cfg: ModelConfig, batch: int, enc_len: int):
    """Decoder self-attn KV cache + precomputed cross K/V over encoder states."""
    H, hd, Ld = cfg.n_heads, cfg.head_dim, cfg.n_layers
    return {
        "self_k": ParamSpec((Ld, batch, cfg.dec_len, H, hd), ("layers", "batch", None, "kv_heads", None), cfg.dtype, init="zeros"),
        "self_v": ParamSpec((Ld, batch, cfg.dec_len, H, hd), ("layers", "batch", None, "kv_heads", None), cfg.dtype, init="zeros"),
        "cross_k": ParamSpec((Ld, batch, enc_len, H, hd), ("layers", "batch", "kv_seq", "kv_heads", None), cfg.dtype, init="zeros"),
        "cross_v": ParamSpec((Ld, batch, enc_len, H, hd), ("layers", "batch", "kv_seq", "kv_heads", None), cfg.dtype, init="zeros"),
    }


def decode_step(cfg: ModelConfig, params, cache, token, pos, ctx: ShardCtx = LOCAL_CTX):
    """One decoder token against cached cross K/V (encoder already run)."""
    B = token.shape[0]
    x = params["embed"].astype(cfg.dtype)[token]
    pos_emb = jax.lax.dynamic_slice_in_dim(
        _sinusoid(cfg.dec_len, cfg.d_model, cfg.dtype), 0, 1, axis=0
    )
    x = x + pos_emb

    def body(x, lp_cache):
        lp, cch = lp_cache
        h = layer_norm(x, lp["ln1_scale"], lp["ln1_bias"])
        q = jnp.einsum("bsd,dhk->bshk", h, lp["self_attn"]["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, lp["self_attn"]["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, lp["self_attn"]["wv"])
        k_cache = jax.lax.dynamic_update_slice(cch["self_k"], k, (0, pos, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(cch["self_v"], v, (0, pos, 0, 0))
        o = attention_decode(q, k_cache, v_cache, pos + 1, rules=ctx.rules)
        x = x + jnp.einsum("bshk,hkd->bsd", o, lp["self_attn"]["wo"])
        # cross-attention over the full cached encoder K/V
        h = layer_norm(x, lp["ln2_scale"], lp["ln2_bias"])
        q = jnp.einsum("bsd,dhk->bshk", h, lp["cross_attn"]["wq"])
        o = attention_decode(q, cch["cross_k"], cch["cross_v"],
                             cch["cross_k"].shape[1], rules=ctx.rules)
        x = x + jnp.einsum("bshk,hkd->bsd", o, lp["cross_attn"]["wo"])
        h = layer_norm(x, lp["ln3_scale"], lp["ln3_bias"])
        h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", h, lp["mlp"]["w_in"]))
        x = x + jnp.einsum("bsf,fd->bsd", h, lp["mlp"]["w_out"])
        return x, {"self_k": k_cache, "self_v": v_cache,
                   "cross_k": cch["cross_k"], "cross_v": cch["cross_v"]}

    x, new_cache = jax.lax.scan(
        body, x,
        (params["dec"], {k: cache[k] for k in ("self_k", "self_v", "cross_k", "cross_v")}))
    x = layer_norm(x, params["dec_final"]["scale"], params["dec_final"]["bias"])
    logits = jnp.einsum("bsd,dv->bsv", x, params["embed"].astype(cfg.dtype).T)
    return lc(logits[:, 0], ("batch", "vocab"), ctx.rules), new_cache
