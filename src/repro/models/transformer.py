"""Decoder-only transformer family: dense (granite-20b, deepseek, codeqwen),
MoE (granite-moe), local/global (gemma3), prefix-LM VLM (paligemma).

Functional design: ``param_specs(cfg)`` declares the pytree of ParamSpec;
``loss_fn`` / ``prefill`` / ``decode_step`` are pure functions lowered under
pjit. Layers are stacked on a leading axis and executed with ``lax.scan``
(small HLO, fast compile at 34..62 layers). gemma3's 5:1 local/global
pattern scans super-blocks of 6; the remainder tail is a second scan.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..parallel.spec import ParamSpec, Rules, logical_constraint as lc
from .common import (
    attention_decode,
    attention_heads_tp,
    attention_seq_tp,
    chunked_cross_entropy,
    ffn,
    moe_combine,
    moe_dispatch,
    moe_expert_compute,
    rms_norm,
    rope,
)
from .config import ModelConfig


# --------------------------------------------------------------------------
# Shard context
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ShardCtx:
    mesh: Optional[Any] = None  # jax.sharding.Mesh
    rules: Optional[Rules] = None
    dp_axes: Tuple[str, ...] = ("data",)
    tp_axis: str = "model"

    @property
    def active(self) -> bool:
        return self.mesh is not None


LOCAL_CTX = ShardCtx()


# --------------------------------------------------------------------------
# Param specs
# --------------------------------------------------------------------------
def _attn_specs(cfg: ModelConfig, L: int) -> Dict[str, ParamSpec]:
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return {
        "wq": ParamSpec((L, D, H, hd), ("layers", "embed", "heads", None), cfg.dtype),
        "wk": ParamSpec((L, D, KV, hd), ("layers", "embed", "kv_heads", None), cfg.dtype),
        "wv": ParamSpec((L, D, KV, hd), ("layers", "embed", "kv_heads", None), cfg.dtype),
        "wo": ParamSpec((L, H, hd, D), ("layers", "heads", None, "embed"), cfg.dtype),
    }


def _ffn_specs(cfg: ModelConfig, L: int) -> Dict[str, ParamSpec]:
    D, F = cfg.d_model, cfg.d_ff
    s = {
        "w_in": ParamSpec((L, D, F), ("layers", "embed", "mlp"), cfg.dtype),
        "w_out": ParamSpec((L, F, D), ("layers", "mlp", "embed"), cfg.dtype),
    }
    if cfg.gated_mlp:
        s["w_gate"] = ParamSpec((L, D, F), ("layers", "embed", "mlp"), cfg.dtype)
    return s


def _moe_specs(cfg: ModelConfig, L: int) -> Dict[str, ParamSpec]:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts_padded
    s = {
        "router": ParamSpec((L, D, cfg.n_experts), ("layers", "embed", None), jnp.float32),
        "w_in": ParamSpec((L, E, D, F), ("layers", "expert", "embed", None), cfg.dtype),
        "w_out": ParamSpec((L, E, F, D), ("layers", "expert", None, "embed"), cfg.dtype),
    }
    if cfg.gated_mlp:
        s["w_gate"] = ParamSpec((L, E, D, F), ("layers", "expert", "embed", None), cfg.dtype)
    return s


def _block_specs(cfg: ModelConfig, L: int, moe: bool) -> Dict[str, Any]:
    D = cfg.d_model
    s: Dict[str, Any] = {
        "ln1": ParamSpec((L, D), ("layers", "embed"), jnp.float32, init="zeros" if cfg.rms_plus_one else "ones"),
        "ln2": ParamSpec((L, D), ("layers", "embed"), jnp.float32, init="zeros" if cfg.rms_plus_one else "ones"),
        "attn": _attn_specs(cfg, L),
    }
    s["moe" if moe else "ffn"] = _moe_specs(cfg, L) if moe else _ffn_specs(cfg, L)
    return s


def param_specs(cfg: ModelConfig) -> Dict[str, Any]:
    D, Vp = cfg.d_model, cfg.vocab_padded
    specs: Dict[str, Any] = {
        "embed": ParamSpec((Vp, D), ("vocab", "embed"), cfg.dtype, scale=1.0),
        "final_norm": ParamSpec((D,), ("embed",), jnp.float32, init="zeros" if cfg.rms_plus_one else "ones"),
    }
    if not cfg.tie_embeddings:
        specs["unembed"] = ParamSpec((D, Vp), ("embed", "vocab"), cfg.dtype)

    if cfg.local_global_period > 0:
        # gemma3: scan super-blocks of (period) layers; remainder tail of local
        period = cfg.local_global_period
        n_super = cfg.n_layers // period
        tail = cfg.n_layers - n_super * period
        specs["blocks"] = {
            f"pos{j}": _block_specs_super(cfg, n_super) for j in range(period)
        }
        if tail:
            specs["tail"] = _block_specs_super(cfg, tail)
    else:
        assert cfg.n_experts == 0 or cfg.moe_period == 1, "use jamba.py for interleaved MoE"
        moe_all = cfg.n_experts > 0
        specs["blocks"] = _block_specs(cfg, cfg.n_layers, moe=moe_all)
    return specs


def _block_specs_super(cfg: ModelConfig, L: int) -> Dict[str, Any]:
    return _block_specs(cfg, L, moe=False)


# --------------------------------------------------------------------------
# Layer application
# --------------------------------------------------------------------------
def _project_qkv(cfg: ModelConfig, lp, x, positions, theta, ctx: ShardCtx):
    q = jnp.einsum("bsd,dhk->bshk", x, lp["attn"]["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, lp["attn"]["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, lp["attn"]["wv"])
    if cfg.use_rope:
        q = rope(q, positions, theta)
        k = rope(k, positions, theta)
    if cfg.attn_mode == "seq_tp":
        # kv must be full-sequence (replicated) for the kv-chunk scan
        k = lc(k, ("batch", None, "kv_heads", None), ctx.rules)
        v = lc(v, ("batch", None, "kv_heads", None), ctx.rules)
    else:
        k = lc(k, ("batch", None, "kv_heads", None), ctx.rules)
        v = lc(v, ("batch", None, "kv_heads", None), ctx.rules)
    return q, k, v


def _attention_block(cfg: ModelConfig, lp, x, *, layer_global: bool,
                     prefix: Optional[int], ctx: ShardCtx, q_offset: int = 0):
    B, S, D = x.shape
    window = None if layer_global else cfg.window
    theta = cfg.rope_theta_global if (layer_global and cfg.local_global_period) else cfg.rope_theta
    positions = q_offset + jnp.arange(S, dtype=jnp.int32)
    h = rms_norm(x, lp["ln1"], eps=cfg.norm_eps, plus_one=cfg.rms_plus_one)
    q, k, v = _project_qkv(cfg, lp, h, positions, theta, ctx)
    kw = dict(causal=True, window=window, prefix=prefix, q_offset=q_offset,
              rules=ctx.rules, scale=cfg.attn_logit_scale,
              unroll=cfg.unroll_scans, probs_bf16=cfg.attn_probs_bf16)
    if cfg.attn_mode == "seq_tp":
        o = attention_seq_tp(q, k, v, kv_chunk=cfg.kv_chunk, **kw)
    else:
        o = attention_heads_tp(q, k, v, q_chunk=cfg.q_chunk, **kw)
    o = jnp.einsum("bshk,hkd->bsd", o, lp["attn"]["wo"])
    return x + residual(cfg, lc(o, ("batch", "act_seq", "embed"), ctx.rules))


def residual(cfg: ModelConfig, y):
    """A branch's output as the residual stream adds it."""
    if cfg.residual_multiplier == 1.0:
        return y
    return y * jnp.asarray(cfg.residual_multiplier, y.dtype)


def _moe_block_fn(cfg: ModelConfig, ctx: ShardCtx):
    """Returns a (possibly shard_mapped) MoE FFN: (x[B,S,D], moe_params)->y.

    Expert parallelism: activations are replicated over the TP axis between
    layers (Megatron convention), so each TP shard dispatches the *same*
    tokens, computes only its expert slice, and a single psum over the TP
    axis combines — one [T, D] all-reduce per MoE layer, no all-to-all.
    """
    E = cfg.n_experts_padded
    cf = cfg.capacity_factor

    def compute(x, router, w_in, w_gate, w_out, ep_rank, ep_size):
        B, S, D = x.shape
        x2d = x.reshape(B * S, D)
        e_loc = E // ep_size
        if cfg.moe_local_dispatch and ep_size > 1:
            # §Perf lever: only materialize the local expert range's buffer
            xe, meta, C = moe_dispatch(
                x2d, router, n_experts=E, top_k=cfg.top_k, capacity_factor=cf,
                renormalize=cfg.router_renormalize,
                expert_lo=ep_rank * e_loc, n_local=e_loc,
            )
            out_e = moe_expert_compute(xe, w_in, w_gate, w_out, cfg.act)
            y = moe_combine(out_e, meta, B * S, D, e_loc, C, x.dtype)
            return y.reshape(B, S, D)
        xe_all, meta, C = moe_dispatch(
            x2d, router, n_experts=E, top_k=cfg.top_k, capacity_factor=cf,
            renormalize=cfg.router_renormalize,
        )
        xe = jax.lax.dynamic_slice_in_dim(xe_all, ep_rank * e_loc, e_loc, axis=0)
        out_e = moe_expert_compute(xe, w_in, w_gate, w_out, cfg.act)
        # place local experts' outputs back into the full [E, C, D] frame
        out_all = jnp.zeros((E, C, D), out_e.dtype)
        out_all = jax.lax.dynamic_update_slice_in_dim(out_all, out_e, ep_rank * e_loc, axis=0)
        y = moe_combine(out_all, meta, B * S, D, E, C, x.dtype)
        return y.reshape(B, S, D)

    if not ctx.active:
        return lambda x, mp: compute(
            x, mp["router"], mp["w_in"], mp.get("w_gate"), mp["w_out"], 0, 1
        )

    mesh, tp = ctx.mesh, ctx.tp_axis
    ep_size = 1 if cfg.moe_replicate_experts else int(mesh.shape[tp])

    def shmap_fn(x, router, w_in, w_gate, w_out):
        if cfg.moe_replicate_experts:
            # §Perf lever: experts replicated -> no EP psum at all
            return compute(x, router, w_in, w_gate, w_out, 0, 1)
        ep_rank = jax.lax.axis_index(tp)
        y = compute(x, router, w_in, w_gate, w_out, ep_rank, ep_size)
        return jax.lax.psum(y, tp)

    def run(x, mp):
        # tokens shard over DP axes only when the batch divides; tiny decode
        # batches fall back to replicated tokens (every shard dispatches the
        # same tokens; expert compute stays sharded; psum still combines).
        n_dp = 1
        for a in ctx.dp_axes:
            n_dp *= int(mesh.shape[a])
        if x.shape[0] % n_dp == 0:
            dp_spec = ctx.dp_axes if len(ctx.dp_axes) > 1 else ctx.dp_axes[0]
        else:
            dp_spec = None
        x_spec = P(dp_spec, None, None)
        w_spec = P(None, None, None) if cfg.moe_replicate_experts else P(tp, None, None)
        in_specs = [x_spec, P(None, None), w_spec]
        if cfg.gated_mlp:
            in_specs.append(w_spec)
        in_specs.append(w_spec)

        args = [x, mp["router"], mp["w_in"]]
        if cfg.gated_mlp:
            args.append(mp["w_gate"])
        args.append(mp["w_out"])
        body = shmap_fn if cfg.gated_mlp else (
            lambda x, r, wi, wo: shmap_fn(x, r, wi, None, wo)
        )
        fn = jax.shard_map(
            body, mesh=mesh, in_specs=tuple(in_specs),
            out_specs=x_spec, check_vma=False,
        )
        return fn(*args)

    return run


def _ffn_or_moe(cfg: ModelConfig, lp, x, is_moe: bool, ctx: ShardCtx):
    h = rms_norm(x, lp["ln2"], eps=cfg.norm_eps, plus_one=cfg.rms_plus_one)
    if is_moe:
        y = _moe_block_fn(cfg, ctx)(h, lp["moe"])
    else:
        y = ffn(h, lp["ffn"]["w_in"], lp["ffn"].get("w_gate"), lp["ffn"]["w_out"],
                act=cfg.act, rules=ctx.rules)
    return x + residual(cfg, y)


def _layer(cfg: ModelConfig, lp, x, *, layer_global: bool, is_moe: bool,
           prefix: Optional[int], ctx: ShardCtx, q_offset: int = 0):
    x = _attention_block(cfg, lp, x, layer_global=layer_global, prefix=prefix,
                         ctx=ctx, q_offset=q_offset)
    return _ffn_or_moe(cfg, lp, x, is_moe, ctx)


def _maybe_remat(cfg: ModelConfig, fn):
    if not cfg.remat:
        return fn
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


# --------------------------------------------------------------------------
# Forward (training) pass
# --------------------------------------------------------------------------
def _embed(cfg: ModelConfig, params, tokens, ctx: ShardCtx,
           prefix_embeds: Optional[jnp.ndarray] = None):
    x = params["embed"].astype(cfg.dtype)[tokens]
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(cfg.dtype), x], axis=1)
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), cfg.dtype)
    if cfg.embed_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embed_multiplier, cfg.dtype)
    return lc(x, ("batch", "act_seq", "embed"), ctx.rules)


def _backbone(cfg: ModelConfig, params, x, ctx: ShardCtx,
              prefix: Optional[int] = None, q_offset: int = 0):
    """Run all layers via scan(s)."""
    if cfg.local_global_period > 0:
        period = cfg.local_global_period

        def super_block(x, lps):
            for j in range(period):
                is_glob = j == period - 1
                x = _layer(cfg, lps[f"pos{j}"], x,
                           layer_global=is_glob, is_moe=False, prefix=prefix,
                           ctx=ctx, q_offset=q_offset)
            return x, None

        x, _ = jax.lax.scan(_maybe_remat(cfg, super_block), x, params["blocks"],
                            unroll=True if cfg.unroll_scans else 1)
        if "tail" in params:
            def tail_block(x, lp):
                return _layer(cfg, lp, x, layer_global=False, is_moe=False,
                              prefix=prefix, ctx=ctx, q_offset=q_offset), None
            x, _ = jax.lax.scan(_maybe_remat(cfg, tail_block), x, params["tail"],
                                unroll=True if cfg.unroll_scans else 1)
        return x

    is_moe = cfg.n_experts > 0 and cfg.moe_period == 1

    def block(x, lp):
        return _layer(cfg, lp, x, layer_global=True, is_moe=is_moe,
                      prefix=prefix, ctx=ctx, q_offset=q_offset), None

    x, _ = jax.lax.scan(_maybe_remat(cfg, block), x, params["blocks"],
                        unroll=True if cfg.unroll_scans else 1)
    return x


def _unembed_weight(cfg: ModelConfig, params):
    if cfg.tie_embeddings:
        return params["embed"].astype(cfg.dtype).T
    return params["unembed"].astype(cfg.dtype)


def loss_fn(cfg: ModelConfig, params, batch, ctx: ShardCtx = LOCAL_CTX):
    """batch: {"tokens": [B, S] int32, "labels": [B, S] int32,
    optional "prefix_embeds": [B, P, D]} -> mean NLL."""
    tokens = batch["tokens"]
    prefix_embeds = batch.get("prefix_embeds")
    prefix = cfg.prefix_len if prefix_embeds is not None else None
    x = _embed(cfg, params, tokens, ctx, prefix_embeds)
    x = _backbone(cfg, params, x, ctx, prefix=prefix)
    x = rms_norm(x, params["final_norm"], plus_one=cfg.rms_plus_one)
    if prefix_embeds is not None:
        x = x[:, cfg.prefix_len:]
    B, S, D = x.shape
    labels = batch["labels"].reshape(B * S)
    return chunked_cross_entropy(
        x.reshape(B * S, D), _unembed_weight(cfg, params), labels,
        chunk=min(cfg.xent_chunk, B * S), rules=ctx.rules,
        unroll=cfg.unroll_scans,
    )


# --------------------------------------------------------------------------
# Serving: prefill + decode
# --------------------------------------------------------------------------
def init_cache_specs(cfg: ModelConfig, batch: int, max_len: int):
    """KV cache ShapeDtypeStructs (per stacked block group)."""
    KV, hd = cfg.n_kv_heads, cfg.head_dim

    def group(L, length):
        return {
            "k": ParamSpec((L, batch, length, KV, hd), ("layers", "batch", "kv_seq", "kv_heads", None), cfg.dtype, init="zeros"),
            "v": ParamSpec((L, batch, length, KV, hd), ("layers", "batch", "kv_seq", "kv_heads", None), cfg.dtype, init="zeros"),
        }

    if cfg.local_global_period > 0:
        period = cfg.local_global_period
        n_super = cfg.n_layers // period
        tail = cfg.n_layers - n_super * period
        local_len = min(max_len, (cfg.window or max_len))
        specs = {"blocks": {}}
        for j in range(period):
            is_glob = j == period - 1
            specs["blocks"][f"pos{j}"] = group(n_super, max_len if is_glob else local_len)
        if tail:
            specs["tail"] = group(tail, local_len)
        return specs
    return {"blocks": group(cfg.n_layers, max_len)}


def _decode_layer(cfg: ModelConfig, lp, cache_lp, x, pos, *, layer_global: bool,
                  is_moe: bool, ctx: ShardCtx):
    """x: [B, 1, D]; cache_lp: {"k": [B, S, KV, hd], "v": ...}. Returns x', cache'."""
    B = x.shape[0]
    window = None if layer_global else cfg.window
    theta = cfg.rope_theta_global if (layer_global and cfg.local_global_period) else cfg.rope_theta
    h = rms_norm(x, lp["ln1"], eps=cfg.norm_eps, plus_one=cfg.rms_plus_one)
    positions = jnp.full((1,), pos, jnp.int32)
    q, k, v = (jnp.einsum("bsd,dhk->bshk", h, lp["attn"][w]) for w in ("wq", "wk", "wv"))
    if cfg.use_rope:
        q, k = rope(q, positions, theta), rope(k, positions, theta)
    S = cache_lp["k"].shape[1]
    slot = pos % S if window is not None else pos  # ring buffer for local layers
    k_cache = jax.lax.dynamic_update_slice(cache_lp["k"], k, (0, slot, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(cache_lp["v"], v, (0, slot, 0, 0))
    cache_len = jnp.minimum(pos + 1, S)
    o = attention_decode(q, k_cache, v_cache, cache_len,
                         window=None,  # ring buffer already bounds local layers
                         rules=ctx.rules, scale=cfg.attn_logit_scale)
    o = jnp.einsum("bshk,hkd->bsd", o, lp["attn"]["wo"])
    x = x + residual(cfg, lc(o, ("batch", None, "embed"), ctx.rules))
    x = _ffn_or_moe(cfg, lp, x, is_moe, ctx)
    return x, {"k": k_cache, "v": v_cache}


def decode_step(cfg: ModelConfig, params, cache, token, pos, ctx: ShardCtx = LOCAL_CTX):
    """token: [B, 1] int32; pos: scalar int32 (current position). Returns
    (logits [B, Vp], new_cache)."""
    x = _embed(cfg, params, token, ctx)

    if cfg.local_global_period > 0:
        period = cfg.local_global_period

        def super_block(x, lps_cache):
            lps, cch = lps_cache
            new_c = {}
            for j in range(period):
                is_glob = j == period - 1
                x, new_c[f"pos{j}"] = _decode_layer(
                    cfg, lps[f"pos{j}"], cch[f"pos{j}"], x, pos,
                    layer_global=is_glob, is_moe=False, ctx=ctx)
            return x, new_c

        x, new_blocks = jax.lax.scan(super_block, x, (params["blocks"], cache["blocks"]),
                                     unroll=True if cfg.unroll_scans else 1)
        new_cache = {"blocks": new_blocks}
        if "tail" in params:
            def tail_block(x, lc_):
                lp, cch = lc_
                x, nc = _decode_layer(cfg, lp, cch, x, pos, layer_global=False,
                                      is_moe=False, ctx=ctx)
                return x, nc
            x, new_tail = jax.lax.scan(tail_block, x, (params["tail"], cache["tail"]),
                                       unroll=True if cfg.unroll_scans else 1)
            new_cache["tail"] = new_tail
    else:
        is_moe = cfg.n_experts > 0 and cfg.moe_period == 1

        def block(x, lp_cache):
            lp, cch = lp_cache
            return _decode_layer(cfg, lp, cch, x, pos, layer_global=True,
                                 is_moe=is_moe, ctx=ctx)

        x, new_blocks = jax.lax.scan(block, x, (params["blocks"], cache["blocks"]),
                                     unroll=True if cfg.unroll_scans else 1)
        new_cache = {"blocks": new_blocks}

    x = rms_norm(x, params["final_norm"], plus_one=cfg.rms_plus_one)
    logits = jnp.einsum("bsd,dv->bsv", x, _unembed_weight(cfg, params))
    logits = lc(logits, ("batch", None, "vocab"), ctx.rules)
    return logits[:, 0], new_cache


def prefill(cfg: ModelConfig, params, tokens, ctx: ShardCtx = LOCAL_CTX,
            prefix_embeds: Optional[jnp.ndarray] = None):
    """Process a full prompt, producing last-position logits. (The KV cache
    write-out variant is exercised via decode; prefill here returns logits —
    the dominant cost is identical.)"""
    prefix = cfg.prefix_len if prefix_embeds is not None else None
    x = _embed(cfg, params, tokens, ctx, prefix_embeds)
    x = _backbone(cfg, params, x, ctx, prefix=prefix)
    x = rms_norm(x, params["final_norm"], plus_one=cfg.rms_plus_one)
    logits = jnp.einsum("bd,dv->bv", x[:, -1], _unembed_weight(cfg, params))
    return lc(logits, ("batch", "vocab"), ctx.rules)
