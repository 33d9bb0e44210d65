"""Mamba-1 selective SSM (falcon-mamba-7b) — TPU-native adaptation.

The CUDA reference fuses the selective scan into one kernel operating in SRAM.
TPU adaptation: channels (d_inner) are the TP axis (all scan/conv/gating ops
are elementwise over channels → zero collectives inside the block; in/out
projections follow the Megatron column/row pattern). The scan itself is
*chunked*: outer ``lax.scan`` over sequence chunks carrying h [B, di, ds];
within a chunk a work-efficient ``associative_scan`` materializes only
[B, chunk, di_local, ds] in VMEM-sized pieces. A Pallas chunk-scan kernel
(kernels/mamba_scan.py) implements the same contract for the TPU hot path.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..parallel.spec import ParamSpec, logical_constraint as lc
from .common import rms_norm
from .config import ModelConfig
from .transformer import ShardCtx, LOCAL_CTX, _embed, _unembed_weight
from .common import chunked_cross_entropy


# --------------------------------------------------------------------------
def mamba_layer_specs(cfg: ModelConfig, L: int) -> Dict[str, ParamSpec]:
    D, di, ds, dr, dc = cfg.d_model, cfg.d_inner, cfg.d_state, cfg.dt_rank, cfg.d_conv
    return {
        "ln": ParamSpec((L, D), ("layers", "embed"), jnp.float32, init="ones"),
        "w_x": ParamSpec((L, D, di), ("layers", "embed", "mlp"), cfg.dtype),
        "w_z": ParamSpec((L, D, di), ("layers", "embed", "mlp"), cfg.dtype),
        "conv_w": ParamSpec((L, di, dc), ("layers", "mlp", None), cfg.dtype, scale=0.5),
        "conv_b": ParamSpec((L, di), ("layers", "mlp"), cfg.dtype, init="zeros"),
        "w_bcdt": ParamSpec((L, di, dr + 2 * ds), ("layers", "mlp", None), cfg.dtype),
        "w_dt": ParamSpec((L, dr, di), ("layers", None, "mlp"), cfg.dtype),
        "b_dt": ParamSpec((L, di), ("layers", "mlp"), jnp.float32, init="zeros"),
        "a_log": ParamSpec((L, di, ds), ("layers", "mlp", "state"), jnp.float32, init="mamba_a"),
        "d_skip": ParamSpec((L, di), ("layers", "mlp"), jnp.float32, init="ones"),
        "w_out": ParamSpec((L, di, D), ("layers", "mlp", "embed"), cfg.dtype),
    }


def param_specs(cfg: ModelConfig) -> Dict[str, Any]:
    D, Vp = cfg.d_model, cfg.vocab_padded
    return {
        "embed": ParamSpec((Vp, D), ("vocab", "embed"), cfg.dtype),
        "final_norm": ParamSpec((D,), ("embed",), jnp.float32, init="ones"),
        "blocks": mamba_layer_specs(cfg, cfg.n_layers),
    }


# --------------------------------------------------------------------------
def _causal_conv(x, w, b, ctx: ShardCtx):
    """Depthwise causal conv. x: [B, S, di]; w: [di, K]; b: [di]."""
    K = w.shape[-1]
    acc = x * w[:, K - 1]
    for i in range(K - 1):
        shift = K - 1 - i
        xi = jnp.pad(x, ((0, 0), (shift, 0), (0, 0)))[:, : x.shape[1]]
        acc = acc + xi * w[:, i]
    return acc + b


def _ssm_scan_chunked(a, b, h0, chunk: int):
    """h_t = a_t*h_{t-1} + b_t over seq axis 1. a,b: [B, S, di, ds].
    Outer scan over chunks, associative scan within."""
    B, S, di, ds = a.shape
    c = min(chunk, S)
    n = S // c
    assert S % c == 0
    a_r = jnp.moveaxis(a.reshape(B, n, c, di, ds), 1, 0)
    b_r = jnp.moveaxis(b.reshape(B, n, c, di, ds), 1, 0)

    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, ar * bl + br

    def outer(h, ab):
        ai, bi = ab
        A_cum, B_cum = jax.lax.associative_scan(combine, (ai, bi), axis=1)
        h_all = B_cum + A_cum * h[:, None]
        return h_all[:, -1], h_all

    h_end, ys = jax.lax.scan(outer, h0, (a_r, b_r))
    ys = jnp.moveaxis(ys, 0, 1).reshape(B, S, di, ds)
    return ys, h_end


def mamba_mixer(cfg: ModelConfig, lp, x, ctx: ShardCtx, h0=None, conv_state=None):
    """Full-sequence mamba mixer. x: [B, S, D] -> [B, S, D].

    If h0/conv_state given (decode), S must be 1 and states are returned.
    """
    B, S, D = x.shape
    di, ds, dr = cfg.d_inner, cfg.d_state, cfg.dt_rank
    xin = jnp.einsum("bsd,de->bse", x, lp["w_x"])
    z = jnp.einsum("bsd,de->bse", x, lp["w_z"])
    xin = lc(xin, ("batch", "act_seq", "mlp"), ctx.rules)
    z = lc(z, ("batch", "act_seq", "mlp"), ctx.rules)

    if conv_state is not None:
        # decode: conv over [conv_state ++ x]
        full = jnp.concatenate([conv_state, xin], axis=1)  # [B, K, di]
        K = lp["conv_w"].shape[-1]
        xc = (full * lp["conv_w"].T[None]).sum(axis=1, keepdims=True) + lp["conv_b"]
        new_conv_state = full[:, 1:]
    else:
        xc = _causal_conv(xin, lp["conv_w"], lp["conv_b"], ctx)
        new_conv_state = None
    xc = jax.nn.silu(xc.astype(jnp.float32)).astype(x.dtype)

    bcdt = jnp.einsum("bse,ef->bsf", xc, lp["w_bcdt"]).astype(jnp.float32)
    dt_in, Bmat, Cmat = jnp.split(bcdt, [dr, dr + ds], axis=-1)
    dt = jax.nn.softplus(
        jnp.einsum("bsr,re->bse", dt_in.astype(x.dtype), lp["w_dt"]).astype(jnp.float32)
        + lp["b_dt"]
    )  # [B, S, di]
    A = -jnp.exp(lp["a_log"])  # [di, ds]
    a = jnp.exp(dt[..., None] * A)  # [B, S, di, ds]
    bx = (dt * xc.astype(jnp.float32))[..., None] * Bmat[:, :, None, :]  # [B,S,di,ds]

    if h0 is None:
        h0 = jnp.zeros((B, di, ds), jnp.float32)
    if S == 1:
        h_all = a * h0[:, None] + bx
        h_end = h_all[:, -1]
    else:
        h_all, h_end = _ssm_scan_chunked(a, bx, h0, cfg.ssm_scan_chunk)

    y = (h_all * Cmat[:, :, None, :]).sum(-1)  # [B, S, di]
    y = y + lp["d_skip"] * xc.astype(jnp.float32)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    out = jnp.einsum("bse,ed->bsd", y, lp["w_out"])
    out = lc(out, ("batch", "act_seq", "embed"), ctx.rules)
    if conv_state is not None:
        return out, (h_end, new_conv_state)
    return out


def _mamba_block(cfg: ModelConfig, lp, x, ctx: ShardCtx):
    return x + mamba_mixer(cfg, lp, rms_norm(x, lp["ln"]), ctx)


# --------------------------------------------------------------------------
def loss_fn(cfg: ModelConfig, params, batch, ctx: ShardCtx = LOCAL_CTX):
    x = _embed(cfg, params, batch["tokens"], ctx)

    def block(x, lp):
        return _mamba_block(cfg, lp, x, ctx), None

    body = jax.checkpoint(block) if cfg.remat else block
    x, _ = jax.lax.scan(body, x, params["blocks"])
    x = rms_norm(x, params["final_norm"])
    B, S, D = x.shape
    return chunked_cross_entropy(
        x.reshape(B * S, D), _unembed_weight(cfg, params),
        batch["labels"].reshape(B * S), chunk=min(cfg.xent_chunk, B * S),
        rules=ctx.rules,
    )


def init_state_specs(cfg: ModelConfig, batch: int):
    di, ds, dc, L = cfg.d_inner, cfg.d_state, cfg.d_conv, cfg.n_layers
    return {
        "h": ParamSpec((L, batch, di, ds), ("layers", "batch", "mlp", "state"), jnp.float32, init="zeros"),
        "conv": ParamSpec((L, batch, dc - 1, di), ("layers", "batch", None, "mlp"), cfg.dtype, init="zeros"),
    }


def decode_step(cfg: ModelConfig, params, state, token, pos, ctx: ShardCtx = LOCAL_CTX):
    """SSM decode: O(1) state, no KV cache. token: [B, 1]."""
    x = _embed(cfg, params, token, ctx)

    def block(x, lp_state):
        lp, (h, conv) = lp_state
        xn = rms_norm(x, lp["ln"])
        out, (h2, conv2) = mamba_mixer(cfg, lp, xn, ctx, h0=h, conv_state=conv)
        return x + out, (h2, conv2)

    x, new_states = jax.lax.scan(
        block, x, (params["blocks"], (state["h"], state["conv"])))
    x = rms_norm(x, params["final_norm"])
    logits = jnp.einsum("bsd,dv->bsv", x, _unembed_weight(cfg, params))
    logits = lc(logits, ("batch", None, "vocab"), ctx.rules)
    return logits[:, 0], {"h": new_states[0], "conv": new_states[1]}


def prefill(cfg: ModelConfig, params, tokens, ctx: ShardCtx = LOCAL_CTX):
    x = _embed(cfg, params, tokens, ctx)

    def block(x, lp):
        return _mamba_block(cfg, lp, x, ctx), None

    x, _ = jax.lax.scan(block, x, params["blocks"])
    x = rms_norm(x, params["final_norm"])
    logits = jnp.einsum("bd,dv->bv", x[:, -1], _unembed_weight(cfg, params))
    return lc(logits, ("batch", "vocab"), ctx.rules)
