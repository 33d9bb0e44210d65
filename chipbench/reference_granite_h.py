"""Plain float32 reference of a granite-4.0-h training step (HF
``granitemoehybrid``: Mamba-2 mixers beside attention without position
encoding, a dense SwiGLU MLP in every layer, no experts).

The block, as the configuration file under ``configs/`` states it:

- ``x0 = embedding_multiplier * E[tokens]``;
- each layer: ``x += residual_multiplier * mixer(RMSNorm(x))``, then
  ``x += residual_multiplier * W_o(silu(W_g h) * W_u h)`` with
  ``h = RMSNorm(x)``;
- logits ``RMSNorm(x) E^T / logits_scaling``; RMSNorm with ``rms_norm_eps``.
- Attention: causal softmax of ``q k^T * attention_multiplier`` over GQA
  groups, no rotary.
- Mamba-2: ``in_proj`` gives z, xBC and dt; ``xBC = silu(conv(xBC) + b)``
  (causal, depthwise) splits into x (heads x head dim), B and C (groups x
  state); ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` from ``S = 0``,
  ``y_t = S_t C_t + D x_t``; then ``w * RMSNorm(y * silu(z))`` over all
  channels (one group) and ``out_proj``.

It is written in straightforward ``jax.numpy``: every matrix product at
float32 ``HIGHEST`` precision, attention one sequence at a time, and the
Mamba-2 state stepped token by token (``lax.scan``, checkpointed in blocks
of ``TIME_BLOCK`` steps so that the chip holds it), not in the chunked form
the program uses.  It imports nothing of the program; the optimizer, the
leaf norms, the readings and the float8 rounding are ``reference_train``'s,
with one reading more (``readings``).

``ssd_outputs`` checks the recurrence alone: an implementation of it (the
program's chunked SSD, or the reference's recurrence with a fault) on
seeded inputs of one row at the configuration's sizes, its output and the
gradient of each input; ``readings`` compares those with the sound
recurrence's (``ssd_gap``).

``init`` makes the initial state from the seed by the published
initialisers (``Model.init_std`` for every matrix and the embedding, Mamba-2's
own for A_log and dt_bias); the driver hands the same weights to the
program as the checkpoint its trainer resumes from.  ``follow`` takes three
AdamW steps from it.

Faults, each put in the program's place against the sound reference:
``quant="fp8"`` rounds every matrix product's operands to float8 e4m3 (the
precision below the configuration's bfloat16); ``fault`` is one of
``state_bf16`` (the state carried in bfloat16), ``no_state_pass`` (the state
reset every ``mamba_chunk_size`` steps, as a chunked form that drops the
pass between chunks), ``no_dx`` (``D x`` left out) and ``no_gate``
(``silu(z)`` left out of the gated norm).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference_train
from chipbench.reference_train import DTYPES, HIGHEST, F32

TIME_BLOCK = 64  # steps of the recurrence between saved states
FAULTS = ("state_bf16", "no_state_pass", "no_dx", "no_gate")


@dataclasses.dataclass(frozen=True)
class Model:
    """The sizes and rules of one configuration file, as run."""

    layer_types: Tuple[str, ...]
    period: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    ssm_heads: int
    ssm_head_dim: int
    groups: int
    d_state: int
    d_conv: int
    chunk: int
    rms_eps: float
    embed_mult: float
    residual_mult: float
    logits_div: float
    attn_scale: float
    weight_dtype: str
    init_std: float

    @classmethod
    def of(cls, config: dict) -> "Model":
        D, H = config["hidden_size"], config["num_attention_heads"]
        return cls(layer_types=tuple(config["layer_types"]),
                   period=config["as_run"]["period"], d_model=D, heads=H,
                   kv_heads=config["num_key_value_heads"], head_dim=D // H,
                   d_ff=config["shared_intermediate_size"], vocab=config["vocab_size"],
                   ssm_heads=config["mamba_n_heads"], ssm_head_dim=config["mamba_d_head"],
                   groups=config["mamba_n_groups"], d_state=config["mamba_d_state"],
                   d_conv=config["mamba_d_conv"], chunk=config["mamba_chunk_size"],
                   rms_eps=config["rms_norm_eps"],
                   embed_mult=float(config["embedding_multiplier"]),
                   residual_mult=config["residual_multiplier"],
                   logits_div=float(config["logits_scaling"]),
                   attn_scale=config["attention_multiplier"],
                   weight_dtype=config["precision"]["weights"],
                   init_std=config["initializer_range"])

    @property
    def n_periods(self) -> int:
        n = len(self.layer_types) // self.period
        if n * self.period != len(self.layer_types) or \
                self.layer_types != self.layer_types[:self.period] * n:
            raise ValueError(f"layer_types do not repeat with period {self.period}")
        return n

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.groups * self.d_state


# -- weights --------------------------------------------------------------------

def leaves(m: Model) -> List[Tuple[str, tuple, str, str]]:
    """(path, shape, dtype, init) of every parameter, in sorted path order.
    Layer j of each period is ``blocks/pos<j>``, stacked over the periods."""
    n, D, F, w = m.n_periods, m.d_model, m.d_ff, m.weight_dtype
    H, KV, hd, hs = m.heads, m.kv_heads, m.head_dim, m.ssm_heads
    out = [("embed", (m.vocab, D), w, "normal"), ("final_norm", (D,), "float32", "ones")]
    for j, kind in enumerate(m.layer_types[:m.period]):
        p = f"blocks/pos{j}/"
        out += [(p + "ln2", (n, D), "float32", "ones"),
                (p + "ffn/w_gate", (n, D, F), w, "normal"),
                (p + "ffn/w_in", (n, D, F), w, "normal"),
                (p + "ffn/w_out", (n, F, D), w, "normal")]
        if kind == "attention":
            out += [(p + "ln1", (n, D), "float32", "ones"),
                    (p + "attn/wq", (n, D, H, hd), w, "normal"),
                    (p + "attn/wk", (n, D, KV, hd), w, "normal"),
                    (p + "attn/wv", (n, D, KV, hd), w, "normal"),
                    (p + "attn/wo", (n, H, hd, D), w, "normal")]
        else:
            p += "mixer/"
            out += [(p + "ln", (n, D), "float32", "ones"),
                    (p + "in_proj", (n, D, m.d_inner + m.conv_dim + hs), w, "normal"),
                    (p + "conv_w", (n, m.conv_dim, m.d_conv), w, "conv"),
                    (p + "conv_b", (n, m.conv_dim), w, "zeros"),
                    (p + "dt_bias", (n, hs), "float32", "dt_bias"),
                    (p + "a_log", (n, hs), "float32", "a_log"),
                    (p + "d_skip", (n, hs), "float32", "ones"),
                    (p + "norm", (n, m.d_inner), "float32", "ones"),
                    (p + "out_proj", (n, m.d_inner, D), w, "normal")]
    return sorted(out)


def _leaf(m: Model, key, shape, kind):
    if kind == "ones":
        return jnp.ones(shape, F32)
    if kind == "zeros":
        return jnp.zeros(shape, F32)
    if kind == "conv":  # PyTorch's Conv1d default: U(-1/sqrt(fan_in), +) with fan_in = d_conv
        bound = 1.0 / math.sqrt(m.d_conv)
        return jax.random.uniform(key, shape, F32, -bound, bound)
    if kind == "a_log":  # A ~ U[1, 16]
        return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
    if kind == "dt_bias":  # softplus^-1(dt), dt log-uniform in [1e-3, 1e-1], >= 1e-4
        dt = jnp.exp(jax.random.uniform(key, shape, F32, math.log(1e-3), math.log(1e-1)))
        dt = jnp.maximum(dt, 1e-4)
        return dt + jnp.log(-jnp.expm1(-dt))
    return jax.random.normal(key, shape, F32) * m.init_std


@functools.partial(jax.jit, static_argnums=(0,))
def _init(m: Model, key) -> Dict[str, jax.Array]:
    spec = leaves(m)
    keys = jax.random.split(key, len(spec))
    return {path: _leaf(m, k, shape, kind).astype(DTYPES[dtype])
            for k, (path, shape, dtype, kind) in zip(keys, spec)}


def init(m: Model, seed: int) -> Dict[str, jax.Array]:
    """The initial weights of ``seed``, made on the device in one call; leaf
    i (sorted path order) draws from key i of ``split(PRNGKey(seed), n)``."""
    return _init(m, jax.random.PRNGKey(seed))


# -- the forward pass -------------------------------------------------------------

def _mm(spec: str, a, b, quant: str):
    if quant == "fp8":
        a, b = reference_train._fp8(a), reference_train._fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _attention(m: Model, q, k, v, quant):
    """Causal attention of one sequence, no position encoding: q [S, H, hd],
    k/v [S, KV, hd]; query head h reads key/value head h // (H / KV)."""
    G = m.heads // m.kv_heads
    k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
    s = _mm("ihd,jhd->hij", q, k, quant) * m.attn_scale
    S = q.shape[0]
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    return _mm("hij,jhd->ihd", p, v, quant)


def _recurrence(m: Model, x, dt, A, Bm, Cm, fault: str):
    """The state-space recurrence stepped token by token.  x [B, S, H, P],
    dt [B, S, H], A [H], Bm/Cm [B, S, G, N] -> y [B, S, H, P] (without D x)."""
    Bsz, S, H, P = x.shape
    R = H // m.groups
    T = math.gcd(S, TIME_BLOCK)
    state_dtype = jnp.bfloat16 if fault == "state_bf16" else F32

    def step(s, inp):
        t, xt, dtt, bt, ct = inp
        if fault == "no_state_pass":
            s = jnp.where(t % m.chunk == 0, 0.0, s).astype(s.dtype)
        bt, ct = jnp.repeat(bt, R, axis=1), jnp.repeat(ct, R, axis=1)  # [B, H, N]
        s = (s.astype(F32) * jnp.exp(dtt * A)[..., None, None]
             + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        y = jnp.einsum("bhpn,bhn->bhp", s, ct, precision=HIGHEST)
        return s.astype(state_dtype), y

    @jax.checkpoint
    def block(s, inp):
        return jax.lax.scan(step, s, inp)

    def blocks(a):  # [B, S, ...] -> [S / T, T, B, ...]
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape(S // T, T, *a.shape[1:])

    inp = (jnp.arange(S).reshape(S // T, T),) + tuple(map(blocks, (x, dt, Bm, Cm)))
    s0 = jnp.zeros((Bsz, H, P, m.d_state), state_dtype)
    _, y = jax.lax.scan(block, s0, inp)
    return jnp.moveaxis(y.reshape(S, Bsz, H, P), 0, 1)


def _mamba(m: Model, lp, h, quant: str, fault: str):
    """The Mamba-2 mixer of h [B, S, D] (normed)."""
    Bsz, S, _ = h.shape
    di, cd, H, P = m.d_inner, m.conv_dim, m.ssm_heads, m.ssm_head_dim
    G, N, K = m.groups, m.d_state, m.d_conv
    zxbcdt = _mm("bsd,de->bse", h, lp["mixer/in_proj"], quant)
    z, xbc, dt = zxbcdt[..., :di], zxbcdt[..., di:di + cd], zxbcdt[..., di + cd:]
    # causal depthwise conv: out_t = b + sum_k w[:, k] xbc_{t - (K - 1) + k}
    pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    w = lp["mixer/conv_w"]
    xbc = lp["mixer/conv_b"] + sum(pad[:, k:k + S] * w[:, k] for k in range(K))
    xbc = jax.nn.silu(xbc)
    x = xbc[..., :di].reshape(Bsz, S, H, P)
    Bm = xbc[..., di:di + G * N].reshape(Bsz, S, G, N)
    Cm = xbc[..., di + G * N:].reshape(Bsz, S, G, N)
    dt = jax.nn.softplus(dt + lp["mixer/dt_bias"])
    A = -jnp.exp(lp["mixer/a_log"])
    y = _recurrence(m, x, dt, A, Bm, Cm, fault)
    if fault != "no_dx":
        y = y + lp["mixer/d_skip"][:, None] * x
    y = y.reshape(Bsz, S, di)
    if fault != "no_gate":
        y = y * jax.nn.silu(z)
    y = _rms(y, lp["mixer/norm"], m.rms_eps)
    return _mm("bse,ed->bsd", y, lp["mixer/out_proj"], quant)


def _attention_mixer(m: Model, lp, h, quant: str):
    q = _mm("bsd,dhk->bshk", h, lp["attn/wq"], quant)
    k = _mm("bsd,dhk->bshk", h, lp["attn/wk"], quant)
    v = _mm("bsd,dhk->bshk", h, lp["attn/wv"], quant)
    o = jax.lax.map(jax.checkpoint(lambda a: _attention(m, *a, quant)), (q, k, v))
    return _mm("bshk,hkd->bsd", o, lp["attn/wo"], quant)


def _layer(m: Model, kind: str, quant: str, fault: str):
    def layer(x, lp):  # x [B, S, D]
        lp = {k: v.astype(F32) for k, v in lp.items()}
        if kind == "attention":
            mix = _attention_mixer(m, lp, _rms(x, lp["ln1"], m.rms_eps), quant)
        else:
            mix = _mamba(m, lp, _rms(x, lp["mixer/ln"], m.rms_eps), quant, fault)
        x = x + m.residual_mult * mix
        h = _rms(x, lp["ln2"], m.rms_eps)
        g = jax.nn.silu(_mm("bsd,df->bsf", h, lp["ffn/w_gate"], quant)) \
            * _mm("bsd,df->bsf", h, lp["ffn/w_in"], quant)
        return x + m.residual_mult * _mm("bsf,fd->bsd", g, lp["ffn/w_out"], quant)

    return jax.checkpoint(layer)


def loss(m: Model, params: dict, tokens, labels, quant: str = "", fault: str = "") -> jax.Array:
    """Mean next-token cross-entropy of ``labels`` [B, S] given ``tokens`` [B, S]."""
    with jax.default_matmul_precision("highest"):
        emb = params["embed"].astype(F32)
        x = m.embed_mult * emb[tokens]
        for i in range(m.n_periods):
            for j, kind in enumerate(m.layer_types[:m.period]):
                p = f"blocks/pos{j}/"
                lp = {k[len(p):]: v[i] for k, v in params.items() if k.startswith(p)}
                x = _layer(m, kind, quant, fault)(x, lp)
        x = _rms(x, params["final_norm"], m.rms_eps)
        B, S, D = x.shape
        n = math.gcd(B * S, 1024)

        @jax.checkpoint
        def nll(args):
            xc, yc = args
            logits = _mm("td,vd->tv", xc, emb, quant) / m.logits_div
            gold = jnp.take_along_axis(logits, yc[:, None], axis=1)[:, 0]
            return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)

        parts = jax.lax.map(nll, (x.reshape(-1, n, D), labels.reshape(-1, n)))
        return jnp.sum(parts) / (B * S)


def recurrence(m: Model, fault: str = ""):
    """The reference's recurrence as an implementation of ``ssd``'s
    signature (x, dt, A, Bm, Cm -> y)."""
    return functools.partial(_recurrence, m, fault=fault)


def ssd_inputs(m: Model, seed: int, seq: int):
    """One row of ``seq`` steps of a Mamba-2 layer's recurrence inputs
    (x, dt, A, Bm, Cm), drawn from ``seed`` at the scale the initial state
    gives them: the projections of a normed input have the deviation
    ``init_std * sqrt(d_model)``, the conv's taps a third of its variance;
    A and dt_bias follow ``init``'s rules.  Also a cotangent for y."""
    H, P, G, N = m.ssm_heads, m.ssm_head_dim, m.groups, m.d_state
    k = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(seed), SSD_STREAM), 5)
    proj = m.init_std * math.sqrt(m.d_model)
    xbc = jax.nn.silu(proj / math.sqrt(3) * jax.random.normal(k[0], (1, seq, H * P + 2 * G * N)))
    x = xbc[..., :H * P].reshape(1, seq, H, P)
    Bm = xbc[..., H * P:H * P + G * N].reshape(1, seq, G, N)
    Cm = xbc[..., H * P + G * N:].reshape(1, seq, G, N)
    dt = jax.nn.softplus(proj * jax.random.normal(k[1], (1, seq, H))
                         + _leaf(m, k[2], (H,), "dt_bias"))
    A = -jnp.exp(_leaf(m, k[3], (H,), "a_log"))
    return (x, dt, A, Bm, Cm), jax.random.normal(k[4], (1, seq, H, P))


SSD_OUTPUTS = ("y", "x", "dt", "A", "B", "C")
SSD_STREAM = 1  # folded into the seed's key, apart from init's draws


@functools.partial(jax.jit, static_argnums=(0, 1, 3))
def _ssd_outputs(m: Model, fn, seed, seq: int):
    args, w = ssd_inputs(m, seed, seq)
    y, vjp = jax.vjp(fn, *args)
    return dict(zip(SSD_OUTPUTS, (y,) + vjp(w)))


def ssd_outputs(m: Model, fn, seed: int, seq: int) -> Dict[str, np.ndarray]:
    """Host copies of ``fn``'s output on ``ssd_inputs`` and of the gradient
    of each input, for the cotangent drawn with them."""
    out = _ssd_outputs(m, fn, jnp.uint32(seed % 2**32), seq)
    return {k: np.asarray(jax.device_get(v), np.float64) for k, v in out.items()}


# -- readings -------------------------------------------------------------------

SSD_LEAVES = ("mixer/a_log", "mixer/dt_bias")


def readings(run: dict, sound: dict) -> Dict[str, float]:
    """``reference_train.readings``, and ``ssd_grad_gap``: over the leaves
    whose gradient flows through the recurrence alone (A_log and dt_bias of
    every Mamba-2 layer, ``ssd_grads``), the largest distance between the
    run's first gradient and the reference's, relative to the reference's
    norm of the leaf.  Those leaves are small beside the median leaf, so
    ``grad_gap`` cannot see them, and a change of the recurrence may turn
    their gradient without changing its norm, so they are compared whole.
    Also ``ssd_gap``: over ``ssd_outputs`` (the recurrence's output and
    input gradients, ``SSD_OUTPUTS``), the largest distance between the
    run's and the sound recurrence's, relative to the sound one's norm.  A state carried in
    bfloat16, or products that read bfloat16 operands, turn these by
    bfloat16's rounding; the end-to-end readings cannot see that beside
    the rounding of the program's bfloat16 activations."""
    out = reference_train.readings(run, sound)
    out["ssd_grad_gap"] = largest_gap(run["ssd_grads"], sound["ssd_grads"])
    out["ssd_gap"] = largest_gap(run["ssd_outputs"], sound["ssd_outputs"])
    return out


def largest_gap(run: dict, sound: dict) -> float:
    """The largest distance between two arrays of the same name, relative
    to the norm of ``sound``'s."""
    gap = max(float(np.linalg.norm(run[k] - v) / np.linalg.norm(v)) for k, v in sound.items())
    return gap if math.isfinite(gap) else math.inf


# -- three steps of AdamW ---------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _grad_fn(m: Model, quant: str, fault: str):
    return jax.jit(jax.value_and_grad(lambda p, t, y: loss(m, p, t, y, quant, fault)))


def follow(m: Model, opt: reference_train.Optimizer, seed: int,
           batches: Sequence[np.ndarray], quant: str = "", fault: str = "") -> dict:
    """Losses of the steps on ``batches`` (token rows of S + 1 ids each), the
    norm of each leaf's first gradient as the optimizer takes it (clipped,
    from the first moment after one step), the first gradient of the
    ``SSD_LEAVES`` whole, and the norm of each leaf's change over all the
    steps."""
    grad = _grad_fn(m, quant, fault)
    update = reference_train._update(opt)
    p = init(m, seed)
    mu = {k: jnp.zeros(v.shape, F32) for k, v in p.items()}
    nu = {k: jnp.zeros(v.shape, F32) for k, v in p.items()}
    losses, grads = [], None
    for step, rows in enumerate(batches):
        rows = jnp.asarray(rows)
        value, g = grad(p, rows[:, :-1], rows[:, 1:])
        losses.append(float(value))
        p, mu, nu = update(p, mu, nu, g, step)
        del g
        if step == 0:
            grads = {k: v / (1 - opt.b1)
                     for k, v in reference_train.leaf_norms(mu).items()}
            ssd = ssd_grads(mu, opt.b1)
    del mu, nu
    change = reference_train.leaf_norms(p, init(m, seed))
    return {"losses": losses, "grad_norms": grads, "change_norms": change, "ssd_grads": ssd}


def ssd_grads(mu: Dict[str, jax.Array], b1: float) -> Dict[str, np.ndarray]:
    """Host copies of the first gradient (as the optimizer takes it) of the
    ``SSD_LEAVES``, from the first moment after one step."""
    return {k: np.asarray(jax.device_get(v), np.float64) / (1 - b1)
            for k, v in mu.items() if k.endswith(SSD_LEAVES)}
