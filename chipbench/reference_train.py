"""Plain float32 reference of a granite-moe training step, and the readings
that compare a run with it.

The model is the one a configuration file under ``configs/`` states, with
the departures that file lists (capacity-based expert dispatch, none of the
published multipliers, the softmax over the padded vocabulary rows), in
straightforward ``jax.numpy``: every matrix product at float32 ``HIGHEST``
precision, each expert applied to every token and weighted by its routing
share, attention one sequence at a time.  It imports nothing of the
program.  It shares the initial weights with the run: ``init`` makes them
from the seed by the published initialiser, and the driver hands the same
weights to the program as the checkpoint its trainer resumes from.

``follow`` takes three AdamW steps from those weights on the rows the run
fed its first three steps.  Parameters stay in the dtype the configuration
states (bfloat16 weights, float32 norms and router) and each update is
computed in float32, as the configuration's optimizer says.  The gradient
of a leaf comes back in the leaf's own dtype, as autodiff gives it.

The control (``quant="fp8"``) computes the same steps with every matrix
product's operands rounded to float8 e4m3, each tensor scaled to the
format's range: the precision below the configuration's bfloat16.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


@dataclasses.dataclass(frozen=True)
class Model:
    """The sizes and rules of one configuration file, as run."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    vocab_rows: int
    experts: int
    expert_rows: int
    top_k: int
    capacity_factor: float
    rope_theta: float
    rms_eps: float
    weight_dtype: str
    init_std: float

    @classmethod
    def of(cls, config: dict) -> "Model":
        run = config["as_run"]
        return cls(layers=config["num_hidden_layers"], d_model=config["hidden_size"],
                   heads=config["num_attention_heads"],
                   kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
                   d_ff=config["intermediate_size"], vocab=config["vocab_size"],
                   vocab_rows=run["vocab_rows"], experts=config["num_local_experts"],
                   expert_rows=run["expert_rows"], top_k=config["num_experts_per_tok"],
                   capacity_factor=run["capacity_factor"],
                   rope_theta=config["rope_theta"], rms_eps=config["rms_norm_eps"],
                   weight_dtype=config["precision"]["weights"],
                   init_std=config["initializer_range"])

    def capacity(self, tokens: int) -> int:
        """Assignments an expert keeps per call: ceil(T x K / E x factor)."""
        return max(1, math.ceil(tokens * self.top_k / self.experts * self.capacity_factor))


@dataclasses.dataclass(frozen=True)
class Optimizer:
    """AdamW with global-norm clipping under a linear warm-up and cosine decay."""

    lr: float
    b1: float
    b2: float
    eps: float
    weight_decay: float
    clip_norm: float
    warmup_steps: int
    min_lr_frac: float
    total_steps: int

    @classmethod
    def of(cls, config: dict, total_steps: int) -> "Optimizer":
        o = config["optimizer"]
        return cls(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                   weight_decay=o["weight_decay"], clip_norm=o["clip_norm"],
                   warmup_steps=o["warmup_steps"], min_lr_frac=o["min_lr_frac"],
                   total_steps=total_steps)

    def lr_scale(self, step):
        s = jnp.asarray(step, F32)
        warm = jnp.minimum(s / max(self.warmup_steps, 1), 1.0)
        prog = jnp.clip((s - self.warmup_steps) / max(self.total_steps - self.warmup_steps, 1),
                        0.0, 1.0)
        return warm * (self.min_lr_frac
                       + (1 - self.min_lr_frac) * 0.5 * (1 + jnp.cos(jnp.pi * prog)))


# -- weights --------------------------------------------------------------------

def leaves(m: Model) -> List[Tuple[str, tuple, str, str]]:
    """(path, shape, dtype, init) of every parameter, in sorted path order."""
    L, D, H, KV, hd = m.layers, m.d_model, m.heads, m.kv_heads, m.head_dim
    E, F, w = m.expert_rows, m.d_ff, m.weight_dtype
    return sorted([
        ("blocks/attn/wk", (L, D, KV, hd), w, "normal"),
        ("blocks/attn/wo", (L, H, hd, D), w, "normal"),
        ("blocks/attn/wq", (L, D, H, hd), w, "normal"),
        ("blocks/attn/wv", (L, D, KV, hd), w, "normal"),
        ("blocks/ln1", (L, D), "float32", "ones"),
        ("blocks/ln2", (L, D), "float32", "ones"),
        ("blocks/moe/router", (L, D, m.experts), "float32", "normal"),
        ("blocks/moe/w_gate", (L, E, D, F), w, "normal"),
        ("blocks/moe/w_in", (L, E, D, F), w, "normal"),
        ("blocks/moe/w_out", (L, E, F, D), w, "normal"),
        ("embed", (m.vocab_rows, D), w, "normal"),
        ("final_norm", (D,), "float32", "ones"),
    ])


@functools.partial(jax.jit, static_argnums=(0,))
def _init(m: "Model", key) -> Dict[str, jax.Array]:
    spec = leaves(m)
    keys = jax.random.split(key, len(spec))
    return {path: (jnp.ones(shape, DTYPES[dtype]) if kind == "ones" else
                   (jax.random.normal(k, shape, F32) * m.init_std).astype(DTYPES[dtype]))
            for k, (path, shape, dtype, kind) in zip(keys, spec)}


def init(m: Model, seed: int) -> Dict[str, jax.Array]:
    """The initial weights of ``seed``, made on the device in one call: every
    matrix N(0, initializer_range), every norm 1; leaf i (sorted path order)
    draws from key i of ``split(PRNGKey(seed), n_leaves)``."""
    return _init(m, jax.random.PRNGKey(seed))


def _blocks(params: dict) -> dict:
    return {k[len("blocks/"):]: v for k, v in params.items() if k.startswith("blocks/")}


# -- the forward pass -------------------------------------------------------------

def _fp8(x):
    """x with its values rounded to float8 e4m3 (scaled so its largest
    magnitude sits at 224, below the format's largest finite value)."""
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    s = jnp.where(amax > 0, 224.0 / amax, 1.0)
    q = jax.lax.reduce_precision(x * s, exponent_bits=4, mantissa_bits=3) / s
    return x + jax.lax.stop_gradient(q - x)  # rounding forward, identity backward


def _mm(spec: str, a, b, quant: str):
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotary embedding of x [S, heads, hd] at positions 0..S-1, the two
    halves of each head rotated against each other."""
    S, _, d = x.shape
    half = d // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None, None] * freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def _attention(m: Model, q, k, v, quant):
    """Causal attention of one sequence: q [S, H, hd], k/v [S, KV, hd];
    query head h reads key/value head h // (H / KV)."""
    G = m.heads // m.kv_heads
    k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
    s = _mm("ihd,jhd->hij", q, k, quant) * m.head_dim ** -0.5
    S = q.shape[0]
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    return _mm("hij,jhd->ihd", p, v, quant)


def route(m: Model, x, router, quant: str = ""):
    """Routing of x [T, D]: the top-k of the router's logits and the softmax
    over them, and which assignments their experts keep: each expert keeps
    its first ``capacity`` assignments in token order."""
    T = x.shape[0]
    logits = _mm("td,de->te", x, router, quant)
    vals, idx = jax.lax.top_k(logits, m.top_k)
    gates = jax.nn.softmax(vals, axis=-1)
    flat = idx.reshape(-1)  # token-major: assignment (t, j) at t * K + j
    onehot = (flat[:, None] == jnp.arange(m.experts)).astype(jnp.int32)
    rank = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1, flat[:, None], axis=1)[:, 0]
    return idx, gates, (rank < m.capacity(T)).reshape(T, m.top_k)


def _moe(m: Model, x, lp, quant):
    """x [T, D]: each expert applied to every token, weighted by its gate
    where it kept the token's assignment and by 0 elsewhere."""
    T = x.shape[0]
    idx, gates, keep = route(m, x, lp["moe/router"], quant)
    share = jnp.zeros((T, m.expert_rows), F32).at[
        jnp.arange(T)[:, None], idx].add(gates * keep)

    def expert(y, e):
        w_gate, w_in, w_out, s = e
        h = jax.nn.silu(_mm("td,df->tf", x, w_gate, quant)) * _mm("td,df->tf", x, w_in, quant)
        return y + s[:, None] * _mm("tf,fd->td", h, w_out, quant), None

    ws = (lp["moe/w_gate"], lp["moe/w_in"], lp["moe/w_out"], share.T)
    y, _ = jax.lax.scan(jax.checkpoint(expert), jnp.zeros_like(x), ws)
    return y


def _layer(m: Model, quant: str):
    def layer(x, lp):  # x [B, S, D]
        lp = {k: v.astype(F32) for k, v in lp.items()}
        B, S, D = x.shape
        h = _rms(x, lp["ln1"], m.rms_eps)
        q = _mm("bsd,dhk->bshk", h, lp["attn/wq"], quant)
        k = _mm("bsd,dhk->bshk", h, lp["attn/wk"], quant)
        v = _mm("bsd,dhk->bshk", h, lp["attn/wv"], quant)

        def one(args):
            qi, ki, vi = args
            return _attention(m, _rope(qi, m.rope_theta), _rope(ki, m.rope_theta), vi, quant)

        o = jax.lax.map(jax.checkpoint(one), (q, k, v))
        x = x + _mm("bshk,hkd->bsd", o, lp["attn/wo"], quant)
        h = _rms(x, lp["ln2"], m.rms_eps)
        return x + _moe(m, h.reshape(B * S, D), lp, quant).reshape(B, S, D), None

    return jax.checkpoint(layer)


def loss(m: Model, params: dict, tokens, labels, quant: str = "") -> jax.Array:
    """Mean next-token cross-entropy of ``labels`` [B, S] given ``tokens``
    [B, S], over every row of the (padded) vocabulary."""
    emb = params["embed"].astype(F32)
    x = emb[tokens]
    x, _ = jax.lax.scan(_layer(m, quant), x, _blocks(params))
    x = _rms(x, params["final_norm"], m.rms_eps)
    B, S, D = x.shape
    n = math.gcd(B * S, 1024)

    @jax.checkpoint
    def nll(args):
        xc, yc = args
        logits = _mm("td,vd->tv", xc, emb, quant)
        gold = jnp.take_along_axis(logits, yc[:, None], axis=1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)

    parts = jax.lax.map(nll, (x.reshape(-1, n, D), labels.reshape(-1, n)))
    return jnp.sum(parts) / (B * S)


# -- three steps of AdamW ---------------------------------------------------------

@jax.jit
def _norms(tree, base):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(F32) - base[k].astype(F32))))
            for k, v in tree.items()}


def leaf_norms(tree: Dict[str, jax.Array], base=None) -> Dict[str, float]:
    """The float32 L2 norm of each leaf, or of its difference from ``base``."""
    if base is None:
        base = {k: jnp.zeros((), v.dtype) for k, v in tree.items()}
    return {k: float(v) for k, v in jax.device_get(_norms(tree, base)).items()}


@functools.lru_cache(maxsize=None)
def _grad_fn(m: Model, quant: str):
    return jax.jit(jax.value_and_grad(lambda p, t, y: loss(m, p, t, y, quant)))


@functools.lru_cache(maxsize=None)
def _update(opt: Optimizer):
    def update(p, mu, nu, g, step):
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(F32))) for x in g.values()))
        scale = jnp.minimum(1.0, opt.clip_norm / jnp.maximum(gnorm, 1e-9))
        t = jnp.asarray(step, F32) + 1.0
        c1, c2 = 1.0 - opt.b1 ** t, 1.0 - opt.b2 ** t
        lr = opt.lr * opt.lr_scale(step)
        out_p, out_mu, out_nu = {}, {}, {}
        for k in p:
            gk = g[k].astype(F32) * scale
            out_mu[k] = opt.b1 * mu[k] + (1 - opt.b1) * gk
            out_nu[k] = opt.b2 * nu[k] + (1 - opt.b2) * gk * gk
            pk = p[k].astype(F32)
            delta = (out_mu[k] / c1) / (jnp.sqrt(out_nu[k] / c2) + opt.eps) \
                + opt.weight_decay * pk
            out_p[k] = (pk - lr * delta).astype(p[k].dtype)
        return out_p, out_mu, out_nu

    return jax.jit(update, donate_argnums=(0, 1, 2))


def follow(m: Model, opt: Optimizer, seed: int, batches: Sequence[np.ndarray],
           quant: str = "") -> dict:
    """Losses of the steps on ``batches`` (token rows of S + 1 ids each), the
    norm of each leaf's first gradient as the optimizer takes it (clipped,
    from the first moment after one step), and the norm of each leaf's
    change over all the steps."""
    grad = _grad_fn(m, quant)
    update = _update(opt)
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
            grads = {k: v / (1 - opt.b1) for k, v in leaf_norms(mu).items()}
    del mu, nu
    change = leaf_norms(p, init(m, seed))
    return {"losses": losses, "grad_norms": grads, "change_norms": change}


# -- readings -------------------------------------------------------------------

def readings(run: dict, ref: dict) -> Dict[str, float]:
    """How far a run's three steps lie from the reference's.

    ``loss_gap``: the largest relative gap of a step's loss.  ``grad_gap`` and
    ``change_gap``: over leaves, the largest gap between the run's norm and
    the reference's, relative to the reference's norm of that leaf or of the
    median leaf, whichever is larger.  ``change_gap`` leaves out leaves whose
    first gradient in the reference is under a thousandth of the median
    leaf's: those move by rounding alone.
    """
    out = {"loss_gap": np.max([abs(a - b) / abs(b)
                               for a, b in zip(run["losses"], ref["losses"])])}
    g_med = float(np.median(list(ref["grad_norms"].values())))
    moved = [k for k, v in ref["grad_norms"].items() if v >= 1e-3 * g_med]
    for name, key, names in (("grad_gap", "grad_norms", list(ref["grad_norms"])),
                             ("change_gap", "change_norms", moved)):
        med = float(np.median([ref[key][k] for k in names]))
        out[name] = np.max([abs(run[key][k] - ref[key][k]) / max(ref[key][k], med)
                            for k in names])
    return {k: (float(v) if math.isfinite(v) else math.inf) for k, v in out.items()}
