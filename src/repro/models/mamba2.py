"""Mamba-2 mixer (granite-4.0-h) as the chunked state-space dual (SSD).

Per head h of ``ssm_head_dim`` channels the layer runs the recurrence

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T,    y_t = S_t C_t + D_h x_t

with S [head_dim, d_state] zero at the start of each row.  ``ssd`` computes
it a chunk of ``ssm_chunk`` steps at a time: inside a chunk as a masked
quadratic form (the decays between every pair of steps), across chunks as a
scan that carries S.  Everything from the conv onwards is float32, the state
included, and the SSD's products run at float32 precision (``HIGHEST``;
the chip's default would read their operands as bfloat16); the projections
take the model's dtype.

Host-visible names (``jax.named_scope``): ``repro.mamba2.in_proj``,
``.conv``, ``.ssd``, ``.norm`` and ``.out_proj``.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ..parallel.spec import ParamSpec, logical_constraint as lc
from .config import ModelConfig
from .mamba import _causal_conv

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def layer_specs(cfg: ModelConfig, L: int) -> Dict[str, ParamSpec]:
    D, di, hs, cd = cfg.d_model, cfg.d_inner, cfg.ssm_heads, cfg.ssm_conv_dim
    if hs * cfg.ssm_head_dim != di:
        raise ValueError(f"{cfg.name}: {hs} Mamba-2 heads of {cfg.ssm_head_dim} "
                         f"do not make d_inner {di}")
    return {
        "ln": ParamSpec((L, D), ("layers", "embed"), F32, init="ones"),
        # columns: z (d_inner), x B C (conv_dim), dt (heads)
        "in_proj": ParamSpec((L, D, di + cd + hs), ("layers", "embed", None), cfg.dtype),
        "conv_w": ParamSpec((L, cd, cfg.d_conv), ("layers", None, None), cfg.dtype, scale=0.5),
        "conv_b": ParamSpec((L, cd), ("layers", None), cfg.dtype, init="zeros"),
        "dt_bias": ParamSpec((L, hs), ("layers", None), F32, init="mamba2_dt"),
        "a_log": ParamSpec((L, hs), ("layers", None), F32, init="mamba2_a"),
        "d_skip": ParamSpec((L, hs), ("layers", None), F32, init="ones"),
        "norm": ParamSpec((L, di), ("layers", "mlp"), F32, init="ones"),
        "out_proj": ParamSpec((L, di, D), ("layers", "mlp", "embed"), cfg.dtype),
    }


def state_specs(cfg: ModelConfig, L: int, batch: int) -> Dict[str, ParamSpec]:
    """Decode state of a stack of ``L`` Mamba-2 layers: the SSM state and
    the conv's last ``d_conv - 1`` inputs."""
    return {
        "ssm": ParamSpec((L, batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.d_state),
                         ("layers", "batch", None, None, "state"), F32, init="zeros"),
        "conv": ParamSpec((L, batch, cfg.d_conv - 1, cfg.ssm_conv_dim),
                          ("layers", "batch", None, None), cfg.dtype, init="zeros"),
    }


def ssd(x, dt, A, Bm, Cm, chunk: int, unroll: bool = False):
    """The SSM part of the mixer (without D x), chunked.

    x [B, S, H, P], dt [B, S, H], A [H], Bm/Cm [B, S, G, N] -> y [B, S, H, P],
    all float32; head h reads group h // (H / G).  A sequence that is not a
    whole number of chunks is padded at its end with steps that change
    nothing before them.
    """
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2:]
    R = H // G
    l = min(chunk, S)
    if S % l:
        pad = l - S % l
        x, dt, Bm, Cm = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
                         for a in (x, dt, Bm, Cm))
        return ssd(x, dt, A, Bm, Cm, chunk, unroll)[:, :S]
    nc = S // l
    # log-decay of each step, cumulated inside its chunk: [B, nc, G, R, l]
    a = jnp.moveaxis((dt * A).reshape(Bsz, nc, l, G, R), 2, -1)
    acs = jnp.cumsum(a, axis=-1)
    xdt = (x * dt[..., None]).reshape(Bsz, nc, l, G, R, P)
    Bc = Bm.reshape(Bsz, nc, l, G, N)
    Cc = Cm.reshape(Bsz, nc, l, G, N)

    # inside a chunk: y_i = sum_{j <= i} exp(acs_i - acs_j) (C_i . B_j) xdt_j
    causal = jnp.tril(jnp.ones((l, l), bool))
    seg = jnp.where(causal, acs[..., :, None] - acs[..., None, :], -jnp.inf)
    cb = jnp.einsum("bclgn,bcsgn->bcgls", Cc, Bc, precision=HIGHEST)
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp", cb[:, :, :, None] * jnp.exp(seg), xdt,
                   precision=HIGHEST)

    # each chunk's own contribution to the state at its end
    decay = jnp.exp(acs[..., -1:] - acs)
    states = jnp.einsum("bclgn,bcgrl,bclgrp->bcgrpn", Bc, decay, xdt, precision=HIGHEST)

    # across chunks: the state entering chunk c
    def carry(s, inp):
        st, dec = inp
        return s * dec[..., None, None] + st, s

    s0 = jnp.zeros((Bsz, G, R, P, N), F32)
    _, prev = jax.lax.scan(carry, s0, (jnp.moveaxis(states, 1, 0),
                                       jnp.moveaxis(jnp.exp(acs[..., -1]), 1, 0)),
                           unroll=True if unroll else 1)
    prev = jnp.moveaxis(prev, 0, 1)
    y = y + jnp.einsum("bclgn,bcgrpn,bcgrl->bclgrp", Cc, prev, jnp.exp(acs),
                       precision=HIGHEST)
    return y.reshape(Bsz, S, H, P)


def _split(cfg: ModelConfig, zxbcdt):
    di, cd = cfg.d_inner, cfg.ssm_conv_dim
    return zxbcdt[..., :di], zxbcdt[..., di:di + cd], zxbcdt[..., di + cd:]


def _heads(cfg: ModelConfig, xbc):
    """x [.., H, P], B [.., G, N], C [.., G, N] of the conv's output."""
    di, G, N = cfg.d_inner, cfg.ssm_groups, cfg.d_state
    lead = xbc.shape[:-1]
    x = xbc[..., :di].reshape(*lead, cfg.ssm_heads, cfg.ssm_head_dim)
    Bm = xbc[..., di:di + G * N].reshape(*lead, G, N)
    Cm = xbc[..., di + G * N:].reshape(*lead, G, N)
    return x, Bm, Cm


def _gated_norm(cfg: ModelConfig, lp, y, z):
    """RMSNorm(y * silu(z)) over all d_inner channels (one group), times w."""
    g = y * jax.nn.silu(z.astype(F32))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + cfg.norm_eps)
    return g * lp["norm"]


def mixer(cfg: ModelConfig, lp, x, ctx, state: Optional[Dict] = None):
    """x [B, S, D] (normed) -> [B, S, D].  With ``state`` (decode, S = 1)
    also returns the next state."""
    B, S, _ = x.shape
    H, P = cfg.ssm_heads, cfg.ssm_head_dim
    with jax.named_scope("repro.mamba2.in_proj"):
        z, xbc, dt = _split(cfg, jnp.einsum("bsd,de->bse", x, lp["in_proj"]))
    with jax.named_scope("repro.mamba2.conv"):
        if state is None:
            xbc = _causal_conv(xbc.astype(F32), lp["conv_w"].astype(F32),
                               lp["conv_b"].astype(F32), ctx)
        else:
            full = jnp.concatenate([state["conv"], xbc], axis=1)  # [B, K, conv_dim]
            new_conv = full[:, 1:]
            xbc = jnp.einsum("bkc,ck->bc", full.astype(F32),
                             lp["conv_w"].astype(F32))[:, None] + lp["conv_b"].astype(F32)
        xs, Bm, Cm = _heads(cfg, jax.nn.silu(xbc))
    with jax.named_scope("repro.mamba2.ssd"):
        dt = jax.nn.softplus(dt.astype(F32) + lp["dt_bias"])
        A = -jnp.exp(lp["a_log"])
        if state is None:
            y = ssd(xs, dt, A, Bm, Cm, cfg.ssm_chunk)
        else:
            R = H // cfg.ssm_groups
            Bh = jnp.repeat(Bm[:, 0], R, axis=1)  # [B, H, N]
            Ch = jnp.repeat(Cm[:, 0], R, axis=1)
            s = (state["ssm"] * jnp.exp(dt[:, 0] * A)[..., None, None]
                 + (dt[:, 0, :, None] * xs[:, 0])[..., None] * Bh[:, :, None, :])
            y = jnp.einsum("bhpn,bhn->bhp", s, Ch, precision=HIGHEST)[:, None]
        y = y + lp["d_skip"][:, None] * xs
    with jax.named_scope("repro.mamba2.norm"):
        y = _gated_norm(cfg, lp, y.reshape(B, S, H * P), z)
    with jax.named_scope("repro.mamba2.out_proj"):
        out = jnp.einsum("bse,ed->bsd", y.astype(x.dtype), lp["out_proj"])
        out = lc(out, ("batch", "act_seq", "embed"), ctx.rules)
    if state is None:
        return out
    return out, {"ssm": s, "conv": new_conv}
