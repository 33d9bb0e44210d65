"""Shared model components: norms, RoPE, attention (two TP modes), FFN, MoE,
and chunked cross-entropy. Everything is a pure function of (config-ish args,
params, activations) so it lowers identically under jit/pjit on any mesh.

Attention TP modes
------------------
- ``heads_tp``  (n_heads % tp == 0): q-chunked online-softmax scan; heads
  sharded over "model". Memory per step: [B, qc, H_loc, S] scores. Lowered
  for a TPU, a call that ``flash_applies`` to runs the Pallas flash kernel
  instead, which keeps scores in VMEM and has its own backward.
- ``seq_tp``    (small-head archs): q-sequence sharded over "model", kv
  replicated; kv-chunked online-softmax scan. Scores [B, S_loc, H, kc].

Both are flash-style (never materialize [S, S]), differentiable (lax.scan),
and masked for causal / sliding-window / prefix-LM.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.flash_attention import flash_attention
from ..parallel.spec import Rules, logical_constraint as lc

NEG_INF = -1e30


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------
def rms_norm(x, scale, eps: float = 1e-6, plus_one: bool = False):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    s = scale.astype(jnp.float32)
    if plus_one:  # gemma convention
        s = 1.0 + s
    return (y * s).astype(dtype)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = x.mean(-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------
def rope(x, positions, theta: float = 10_000.0):
    """x: [..., S, H, D]; positions: [..., S] (broadcastable)."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., :, None, None].astype(jnp.float32) * freq  # [..., S, 1, half]
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Masking
# --------------------------------------------------------------------------
def _mask_bias(q_pos, k_pos, causal: bool, window: Optional[int], prefix: Optional[int]):
    """Additive bias [*q, *k] given global positions (int32 arrays)."""
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    ok = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
    if causal:
        allowed = kp <= qp
        if prefix is not None:
            allowed = allowed | (kp < prefix)
        ok &= allowed
    if window is not None:
        ok &= kp > qp - window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


# --------------------------------------------------------------------------
# Attention — heads_tp mode (q-chunked scan, heads sharded)
# --------------------------------------------------------------------------
def attention_heads_tp(
    q, k, v, *,
    causal: bool = True,
    window: Optional[int] = None,
    prefix: Optional[int] = None,
    q_offset: int = 0,
    q_chunk: int = 512,
    rules: Optional[Rules] = None,
    scale: Optional[float] = None,
):
    """q: [B, Sq, H, D]; k/v: [B, Sk, KVH, D] -> [B, Sq, H, D].

    Lowered for a TPU, a call that ``flash_applies`` to runs the Pallas flash
    kernel; every other call, and every lowering for another platform, runs
    the query-chunk scan."""
    scale = scale or q.shape[-1] ** -0.5
    scan = functools.partial(
        _heads_tp_scan, causal=causal, window=window, prefix=prefix,
        q_offset=q_offset, q_chunk=q_chunk, rules=rules, scale=scale)
    if not flash_applies(q.shape, k.shape, causal=causal, prefix=prefix,
                         q_offset=q_offset, rules=rules):
        return scan(q, k, v)
    flash = functools.partial(flash_attention, causal=True, window=window, scale=scale)
    return jax.lax.platform_dependent(q, k, v, tpu=flash, default=scan)


def _heads_tp_scan(q, k, v, *, causal, window, prefix, q_offset, q_chunk, rules,
                   scale):
    """``attention_heads_tp``'s query-chunk scan."""
    B, Sq, H, D = q.shape
    Sk, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    qc = min(q_chunk, Sq)
    n_chunks = Sq // qc
    assert Sq % qc == 0, (Sq, qc)

    # Constrain the 4D [B,S,H,D] view (H = KVH*G shards over "model"); the
    # grouped 5/6D views inherit the split sharding via propagation. The seq
    # dim is deliberately unconstrained here: under sequence-parallel rules
    # (act_seq="model") the residual stream is seq-sharded between layers and
    # XLA inserts the all-gather/reduce-scatter pair at the block boundary.
    q = lc(q, ("batch", None, "heads", None), rules)
    q = q.reshape(B, n_chunks, qc, KVH, G, D)
    k_pos = jnp.arange(Sk, dtype=jnp.int32)

    def chunk_body(carry, xs):
        ci, qi = xs  # qi: [B, qc, KVH, G, D]
        s = jnp.einsum("bqhgd,bshd->bhgqs", qi.astype(jnp.float32) * scale,
                       k.astype(jnp.float32))
        q_pos = q_offset + ci * qc + jnp.arange(qc, dtype=jnp.int32)
        s = s + _mask_bias(q_pos, k_pos, causal, window, prefix)
        m = jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        p = p / jnp.maximum(l, 1e-30)
        o = jnp.einsum("bhgqs,bshd->bqhgd", p, v.astype(jnp.float32))
        return carry, o.astype(q.dtype)

    _, out = jax.lax.scan(
        chunk_body, None, (jnp.arange(n_chunks), jnp.moveaxis(q, 1, 0)))
    out = jnp.moveaxis(out, 0, 1).reshape(B, Sq, H, D)
    return lc(out, ("batch", None, "heads", None), rules)


def flash_applies(q_shape, k_shape, *, causal: bool, prefix: Optional[int],
                  q_offset, rules: Optional[Rules]) -> bool:
    """Whether the Pallas flash kernel (``kernels/flash_attention.py``)
    serves an ``attention_heads_tp`` call where it is lowered for a TPU: a
    call that no mesh shards, causal without a prefix, queries aligned with
    the keys, rows a multiple of 128 and a head size of 64, 128 or 256."""
    _, Sq, _, D = q_shape
    return (rules is None and causal and prefix is None
            and isinstance(q_offset, int) and q_offset == 0
            and Sq == k_shape[1] and Sq % 128 == 0 and D in (64, 128, 256))


# --------------------------------------------------------------------------
# Attention — seq_tp mode (kv-chunked scan, q-sequence sharded)
# --------------------------------------------------------------------------
def attention_seq_tp(
    q, k, v, *,
    causal: bool = True,
    window: Optional[int] = None,
    prefix: Optional[int] = None,
    q_offset: int = 0,
    kv_chunk: int = 1024,
    rules: Optional[Rules] = None,
    scale: Optional[float] = None,
):
    """Online-softmax over kv chunks; q seq dim stays sharded ("act_seq")."""
    B, Sq, H, D = q.shape
    Sk, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    scale = scale or D ** -0.5
    kc = min(kv_chunk, Sk)
    n_chunks = Sk // kc
    assert Sk % kc == 0, (Sk, kc)

    q5 = q.reshape(B, Sq, KVH, G, D).astype(jnp.float32) * scale
    q5 = lc(q5, ("batch", "act_seq", "kv_heads", "heads", None), rules)
    q_pos = q_offset + jnp.arange(Sq, dtype=jnp.int32)

    k_r = jnp.moveaxis(k.reshape(B, n_chunks, kc, KVH, D), 1, 0)
    v_r = jnp.moveaxis(v.reshape(B, n_chunks, kc, KVH, D), 1, 0)

    def body(carry, xs):
        m, l, acc = carry  # m,l: [B, Sq, KVH, G]; acc: [B, Sq, KVH, G, D]
        ci, ki, vi = xs
        s = jnp.einsum("bqhgd,bshd->bqhgs", q5, ki.astype(jnp.float32))
        k_pos = ci * kc + jnp.arange(kc, dtype=jnp.int32)
        bias = _mask_bias(q_pos, k_pos, causal, window, prefix)  # [Sq, kc]
        s = s + bias[None, :, None, None, :]
        m_new = jnp.maximum(m, jax.lax.stop_gradient(jnp.max(s, axis=-1)))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bqhgs,bshd->bqhgd", p, vi.astype(jnp.float32))
        carry = (m_new, l_new, lc(acc_new, ("batch", "act_seq", "kv_heads", "heads", None), rules))
        return carry, None

    m0 = jnp.full((B, Sq, KVH, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, KVH, G), jnp.float32)
    a0 = jnp.zeros((B, Sq, KVH, G, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (jnp.arange(n_chunks), k_r, v_r))
    out = (acc / jnp.maximum(l, 1e-30)[..., None]).reshape(B, Sq, H, D).astype(q.dtype)
    return lc(out, ("batch", "act_seq", "heads", None), rules)


# --------------------------------------------------------------------------
# Decode attention (single query position against a cache)
# --------------------------------------------------------------------------
def attention_decode(q, k_cache, v_cache, cache_len, *,
                     window: Optional[int] = None,
                     rules: Optional[Rules] = None,
                     scale: Optional[float] = None):
    """q: [B, 1, H, D]; caches: [B, S, KVH, D]; cache_len: effective length.

    Attends over cache[0:cache_len] (+ the new position itself must already
    be written into the cache). Softmax over a (possibly sharded) S axis —
    XLA inserts the max/sum all-reduces automatically.
    """
    B, _, H, D = q.shape
    S, KVH = k_cache.shape[1], k_cache.shape[2]
    G = H // KVH
    scale = scale or D ** -0.5
    q5 = q.reshape(B, KVH, G, D).astype(jnp.float32) * scale
    s = jnp.einsum("bhgd,bshd->bhgs", q5, k_cache.astype(jnp.float32))
    pos = jnp.arange(S, dtype=jnp.int32)
    ok = pos[None, :] < cache_len  # [1, S] or [B, S]
    if window is not None:
        ok = ok & (pos[None, :] > cache_len - 1 - window)
    s = jnp.where(ok[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, 1, H, D).astype(q.dtype)


# --------------------------------------------------------------------------
# FFN (dense) — SwiGLU / GeGLU / GELU-mlp
# --------------------------------------------------------------------------
def ffn(x, w_in, w_gate, w_out, *, act: str = "silu", rules: Optional[Rules] = None):
    """x: [B, S, D]; w_in/w_gate: [D, F]; w_out: [F, D]."""
    h = jnp.einsum("bsd,df->bsf", x, w_in)
    if w_gate is not None:
        g = jnp.einsum("bsd,df->bsf", x, w_gate)
        h = _activate(g, act) * h
    else:
        h = _activate(h, act)
    h = lc(h, ("batch", None, "mlp"), rules)
    out = jnp.einsum("bsf,fd->bsd", h, w_out)
    return lc(out, ("batch", "act_seq", "embed"), rules)


def _activate(x, act: str):
    if act == "silu":
        return jax.nn.silu(x)
    if act == "gelu":
        return jax.nn.gelu(x)
    if act == "relu":
        return jax.nn.relu(x)
    raise ValueError(act)


# --------------------------------------------------------------------------
# MoE — top-k routing, sort-based dropless-ish dispatch with capacity,
# expert-parallel over "model" via replicated-activation + psum combine.
# --------------------------------------------------------------------------
def moe_dispatch(x2d, router_w, *, n_experts: int, top_k: int,
                 capacity_factor: float = 1.25, renormalize: bool = True):
    """Top-k routing + sort-based capacity dispatch. x2d: [T, D].

    Returns (xe [E, C, D], dispatch_meta, C). ``n_experts`` may exceed the
    router's width (padded experts receive no traffic — the top_k indices only
    span router_w.shape[1] real experts).
    """
    T, D = x2d.shape
    n_real = router_w.shape[1]
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32), router_w.astype(jnp.float32))
    gate_vals, expert_idx = jax.lax.top_k(logits, top_k)  # [T, K]
    if renormalize:
        gate_vals = jax.nn.softmax(gate_vals, axis=-1)
    else:
        gate_vals = jax.nn.sigmoid(gate_vals)

    K = top_k
    flat_e = expert_idx.reshape(-1).astype(jnp.int32)  # [T*K]
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
    flat_g = gate_vals.reshape(-1)

    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    # rank of each assignment within its expert group
    starts = jnp.searchsorted(se, jnp.arange(n_experts, dtype=se.dtype), side="left")
    pos_in_e = jnp.arange(T * K, dtype=jnp.int32) - starts[se].astype(jnp.int32)

    C = max(1, int(np.ceil(T * K / max(n_real, 1) * capacity_factor)))
    keep = pos_in_e < C
    slot = jnp.where(keep, se * C + pos_in_e, n_experts * C)  # overflow -> dropped

    # dispatch: buffer [E*C(+1), D]
    buf = jnp.zeros((n_experts * C + 1, D), x2d.dtype)
    buf = buf.at[slot].set(jnp.where(keep[:, None], x2d[st], 0), mode="drop")
    xe = buf[: n_experts * C].reshape(n_experts, C, D)

    return xe, (slot, st, sg, keep), C


def moe_expert_compute(xe, w_in, w_gate, w_out, act: str = "silu"):
    """xe: [E_loc, C, D] -> [E_loc, C, D]."""
    h = jnp.einsum("ecd,edf->ecf", xe, w_in)
    if w_gate is not None:
        g = jnp.einsum("ecd,edf->ecf", xe, w_gate)
        h = _activate(g, act) * h
    else:
        h = _activate(h, act)
    return jnp.einsum("ecf,efd->ecd", h, w_out)


def moe_combine(out_e_all, dispatch_meta, T: int, D: int, n_experts: int, C: int, dtype):
    """Scatter expert outputs back to token order with gate weights."""
    slot, st, sg, keep = dispatch_meta
    flat = out_e_all.reshape(n_experts * C, -1)
    padded = jnp.concatenate([flat, jnp.zeros((1, flat.shape[1]), flat.dtype)], 0)
    contrib = padded[slot] * (sg * keep).astype(flat.dtype)[:, None]
    y = jnp.zeros((T, D), flat.dtype).at[st].add(contrib)
    return y.astype(dtype)


# --------------------------------------------------------------------------
# Chunked softmax cross-entropy (big-vocab safe)
# --------------------------------------------------------------------------
def chunked_cross_entropy(x2d, unembed, labels, *, chunk: int = 4096,
                          rules: Optional[Rules] = None, z_loss: float = 0.0):
    """x2d: [T, D] hidden; unembed: [D, V]; labels: [T] int32. Mean NLL.

    Scans token chunks so the [chunk, V] logits tensor never materializes for
    all T at once; body is rematerialized in backward.  Chunk ``j`` holds the
    strided tokens ``j, j+n, j+2n, ...``: the token axis is split as
    ``[c, n]`` so its (batch) sharding stays on the ``c`` axis inside the body,
    and the scanned axis ``n`` is replicated, as ``lax.scan`` requires.
    """
    T, D = x2d.shape
    c = min(chunk, T)
    while T % c:  # largest divisor of T not exceeding the requested chunk
        c -= 1
    n = T // c
    xs = (jnp.swapaxes(x2d.reshape(c, n, D), 0, 1), labels.reshape(c, n).T)

    @jax.checkpoint
    def body(tot, xs):
        xc, yc = xs
        logits = jnp.einsum("td,dv->tv", xc, unembed).astype(jnp.float32)
        logits = lc(logits, ("act_seq", "vocab"), rules)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yc[:, None].astype(jnp.int32), axis=-1)[:, 0]
        nll = (lse - gold).sum()
        if z_loss:
            nll = nll + z_loss * (lse ** 2).sum()
        return tot + nll, None

    tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), xs)
    return tot / T
