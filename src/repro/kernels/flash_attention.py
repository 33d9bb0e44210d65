"""Flash attention for TPU (pl.pallas_call + BlockSpec VMEM tiling), with a
backward (``jax.custom_vjp``).

Design (TPU-native, not a CUDA port):
- Inside, queries, outputs and their gradients are laid out [B*H, S, D],
  keys and values [B*KVH, S, D]. Query head ``bh`` reads key/value head
  ``bh // G`` through the ``index_map`` (GQA without copies of k and v).
- Forward: grid = (B*H, n_q_blocks, n_kv_blocks); the kv-block axis is the
  innermost, sequentially-executed grid dimension on TPU, so the
  online-softmax state (m, l, acc) lives in VMEM scratch and persists across
  kv steps. Scores and probabilities never reach HBM; the kernel writes o
  and the float32 logsumexp of each row.
- Backward, for the causal mask without a prefix (the mask training
  takes): ``di = rowsum(dO * O)`` is computed beforehand. The dq kernel
  (grid (B*H, n_q, n_kv)) and the dkv kernel (grid (B*KVH, n_kv, G, n_q))
  recompute p from q, k and the saved logsumexp; the dkv kernel sums over
  the G query heads of each kv head in VMEM, and works on transposed scores
  [kv_block, q_block] so that the logsumexp and di are rows.
- The MXU takes the inputs in their own dtype and accumulates in float32;
  the softmax runs in float32.
- Masks: causal, sliding-window, prefix-LM, computed from global indices,
  on the blocks that hold a masked score only. A block wholly past the
  causal diagonal or wholly before the window does no work (``pl.when``),
  and its ``index_map`` is clamped to a block that is needed, so that no
  DMA is issued for it.
- Blocks: the largest of 512, 256 and 128 that divides the sequence, unless
  the caller asks for others.

The forward and the backward run under the named scope ``SCOPE``. The
reference oracle is ref.py::attention_reference; tests sweep shapes, dtypes
and gradients in interpret mode (CPU) against it.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
SCOPE = "repro.attention.flash"
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b


def block_for(n: int) -> int:
    """The largest of 512, 256 and 128 that divides ``n``; ``n`` where none does."""
    for b in (512, 256, 128):
        if n % b == 0:
            return b
    return n


class _Plan(NamedTuple):
    causal: bool
    window: Optional[int]
    prefix: Optional[int]
    scale: float
    q_block: int
    kv_block: int
    groups: int
    interpret: bool


def _kv_blocks(qi, p: _Plan, n_kv: int):
    """First and last kv block that query block ``qi`` attends to."""
    lo, hi = 0, n_kv - 1
    if p.causal and p.prefix is None:
        hi = jnp.minimum(hi, (qi * p.q_block + p.q_block - 1) // p.kv_block)
    if p.window is not None:
        lo = jnp.maximum(qi * p.q_block - p.window + 1, 0) // p.kv_block
    return lo, hi


def _q_blocks(ki, p: _Plan, n_q: int):
    """First and last query block that attends to kv block ``ki``, under the
    causal mask without a prefix that the backward serves."""
    lo, hi = (ki * p.kv_block) // p.q_block, n_q - 1
    if p.window is not None:
        hi = jnp.minimum(hi, ((ki + 1) * p.kv_block + p.window - 2) // p.q_block)
    return lo, hi


def _clamp(i, lo, hi):
    return jnp.minimum(jnp.maximum(i, lo), hi)


def _whole(qi, ki, p: _Plan):
    """Whether no score of block (qi, ki) is masked: ``True`` or ``False``
    where that holds for every block, else a traced bool."""
    if p.causal and p.prefix is not None:
        return False
    whole = True
    if p.causal:
        whole = (ki + 1) * p.kv_block - 1 <= qi * p.q_block
    if p.window is not None:
        whole = whole & (ki * p.kv_block > qi * p.q_block + p.q_block - 1 - p.window)
    return whole


def _run_block(needed, whole, step):
    """Run ``step(masked)`` on a needed block, with the mask only where a
    score of the block is masked."""
    if isinstance(whole, bool):
        pl.when(needed)(lambda: step(not whole))
        return
    pl.when(needed & whole)(lambda: step(False))
    pl.when(needed & jnp.logical_not(whole))(lambda: step(True))


def _masked(s, q_pos, k_pos, p: _Plan):
    ok = None
    if p.causal:
        ok = k_pos <= q_pos
        if p.prefix is not None:
            ok = ok | (k_pos < p.prefix)
    if p.window is not None:
        inside = k_pos > q_pos - p.window
        ok = inside if ok is None else ok & inside
    return s if ok is None else jnp.where(ok, s, NEG_INF)


def _lanes(x, n: int):
    """A [rows, LANES] value whose lanes are equal, as [rows, n]."""
    if n % LANES == 0:
        return jnp.tile(x, (1, n // LANES))
    return x[:, :n]


def _scaled(q, p: _Plan):
    """q times the softmax scale, in q's dtype: the operand of every score
    product (the scan path rounds the same product to the MXU's input)."""
    return (q.astype(jnp.float32) * p.scale).astype(q.dtype)


def _iota(shape, dim, start):
    return start + jax.lax.broadcasted_iota(jnp.int32, shape, dim)


# ----------------------------------------------------------------- forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                p: _Plan, n_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    lo, hi = _kv_blocks(qi, p, n_kv)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _step(masked: bool):
        q, k, v = _scaled(q_ref[0], p), k_ref[0], v_ref[0]
        s = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32)
        if masked:
            shape = s.shape
            s = _masked(s, _iota(shape, 0, qi * p.q_block), _iota(shape, 1, ki * p.kv_block), p)
        m_prev, l_prev = m_scr[...], l_scr[...]  # [q_block, LANES]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        e = jnp.exp(s - _lanes(m_new, p.kv_block))
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = corr * l_prev + jnp.sum(e, axis=1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * _lanes(corr, acc_scr.shape[1]) + jax.lax.dot_general(
            e.astype(v.dtype), v, _NN, preferred_element_type=jnp.float32)

    _run_block((ki >= lo) & (ki <= hi), _whole(qi, ki, p), _step)

    @pl.when(ki == n_kv - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / _lanes(l, acc_scr.shape[1])).astype(o_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(l)


def _forward(q, k, v, p: _Plan):
    """q: [B*H, Sq, D]; k/v: [B*KVH, Skv, D] -> o [B*H, Sq, D] and the
    logsumexp of each row, [B*H, Sq, LANES] float32 (lanes equal)."""
    BH, Sq, D = q.shape
    Skv = k.shape[1]
    n_q, n_kv = Sq // p.q_block, Skv // p.kv_block

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki):
        lo, hi = _kv_blocks(qi, p, n_kv)
        return (bh // p.groups, _clamp(ki, lo, hi), 0)

    return pl.pallas_call(
        functools.partial(_fwd_kernel, p=p, n_kv=n_kv),
        grid=(BH, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, p.q_block, D), q_map),
            pl.BlockSpec((1, p.kv_block, D), kv_map),
            pl.BlockSpec((1, p.kv_block, D), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, p.q_block, D), q_map),
            pl.BlockSpec((1, p.q_block, LANES), q_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((BH, Sq, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((p.q_block, LANES), jnp.float32),
            pltpu.VMEM((p.q_block, LANES), jnp.float32),
            pltpu.VMEM((p.q_block, D), jnp.float32),
        ],
        interpret=p.interpret,
    )(q, k, v)


# ---------------------------------------------------------------- backward
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, dq_scr, *,
               p: _Plan, n_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    lo, hi = _kv_blocks(qi, p, n_kv)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _step(masked: bool):
        q, k, v, do = _scaled(q_ref[0], p), k_ref[0], v_ref[0], do_ref[0]
        s = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32)
        if masked:
            shape = s.shape
            s = _masked(s, _iota(shape, 0, qi * p.q_block), _iota(shape, 1, ki * p.kv_block), p)
        prob = jnp.exp(s - jnp.expand_dims(lse_ref[0], -1))
        dp = jax.lax.dot_general(do, v, _NT, preferred_element_type=jnp.float32)
        ds = prob * (dp - jnp.expand_dims(di_ref[0], -1))
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, _NN, preferred_element_type=jnp.float32)

    _run_block((ki >= lo) & (ki <= hi), _whole(qi, ki, p), _step)

    @pl.when(ki == n_kv - 1)
    def _finish():
        dq_ref[0] = (dq_scr[...] * p.scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, p: _Plan, n_q: int):
    ki = pl.program_id(1)
    g = pl.program_id(2)
    qi = pl.program_id(3)
    lo, hi = _q_blocks(ki, p, n_q)

    @pl.when((g == 0) & (qi == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _step(masked: bool):
        q, k, v, do = _scaled(q_ref[0], p), k_ref[0], v_ref[0], do_ref[0]
        st = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)
        if masked:
            shape = st.shape  # [kv_block, q_block]
            st = _masked(st, _iota(shape, 1, qi * p.q_block), _iota(shape, 0, ki * p.kv_block), p)
        pt = jnp.exp(st - lse_ref[...])
        dv_scr[...] += jax.lax.dot_general(
            pt.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
        dst = pt * (dpt - di_ref[...])
        dk_scr[...] += jax.lax.dot_general(
            dst.astype(q.dtype), q, _NN, preferred_element_type=jnp.float32)

    _run_block((qi >= lo) & (qi <= hi), _whole(qi, ki, p), _step)

    @pl.when((g == p.groups - 1) & (qi == n_q - 1))
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _backward(q, k, v, do, lse, di, p: _Plan):
    """Gradients of q, k and v; ``lse`` and ``di`` are [B*H, 1, Sq] float32."""
    BH, Sq, D = q.shape
    BKV, Skv = k.shape[0], k.shape[1]
    n_q, n_kv = Sq // p.q_block, Skv // p.kv_block
    G = p.groups

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def row_map(bh, qi, ki):
        return (bh, 0, qi)

    def kv_map(bh, qi, ki):
        lo, hi = _kv_blocks(qi, p, n_kv)
        return (bh // G, _clamp(ki, lo, hi), 0)

    q_spec = pl.BlockSpec((1, p.q_block, D), q_map)
    kv_spec = pl.BlockSpec((1, p.kv_block, D), kv_map)
    row_spec = pl.BlockSpec((None, 1, p.q_block), row_map)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, p=p, n_kv=n_kv),
        grid=(BH, n_q, n_kv),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((p.q_block, D), jnp.float32)],
        interpret=p.interpret,
    )(q, k, v, do, lse, di)

    def head_q(bk, ki, g, qi):
        lo, hi = _q_blocks(ki, p, n_q)
        return bk * G + g, _clamp(qi, lo, hi)

    def qt_map(bk, ki, g, qi):
        h, i = head_q(bk, ki, g, qi)
        return (h, i, 0)

    def rowt_map(bk, ki, g, qi):
        h, i = head_q(bk, ki, g, qi)
        return (h, 0, i)

    def own_map(bk, ki, g, qi):
        return (bk, ki, 0)

    qt_spec = pl.BlockSpec((1, p.q_block, D), qt_map)
    own_spec = pl.BlockSpec((1, p.kv_block, D), own_map)
    rowt_spec = pl.BlockSpec((None, 1, p.q_block), rowt_map)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, p=p, n_q=n_q),
        grid=(BKV, n_kv, G, n_q),
        in_specs=[qt_spec, own_spec, own_spec, qt_spec, rowt_spec, rowt_spec],
        out_specs=[own_spec, own_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((p.kv_block, D), jnp.float32),
                        pltpu.VMEM((p.kv_block, D), jnp.float32)],
        interpret=p.interpret,
    )(q, k, v, do, lse, di)
    return dq, dk, dv


# -------------------------------------------------------------- custom_vjp
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _attend(q, k, v, p: _Plan):
    return _forward(q, k, v, p)[0]


def _attend_fwd(q, k, v, p: _Plan):
    o, lse = _forward(q, k, v, p)
    return o, (q, k, v, o, lse[:, None, :, 0])


def _attend_bwd(p: _Plan, res, do):
    q, k, v, o, lse = res
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)[:, None, :]
    return _backward(q, k, v, do, lse, di, p)


_attend.defvjp(_attend_fwd, _attend_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "prefix", "scale", "q_block", "kv_block", "interpret"),
)
def flash_attention(
    q, k, v, *,
    causal: bool = True,
    window: Optional[int] = None,
    prefix: Optional[int] = None,
    scale: Optional[float] = None,
    q_block: Optional[int] = None,
    kv_block: Optional[int] = None,
    interpret: bool = False,
):
    """q: [B, Sq, H, D]; k/v: [B, Skv, KVH, D] -> [B, Sq, H, D].

    Differentiable in q, k and v where causal without a prefix; other masks
    run the forward alone. GQA: query head h reads kv head h // G. Blocks
    default to ``block_for`` of each sequence length.
    """
    B, Sq, H, D = q.shape
    Skv, KVH = k.shape[1], k.shape[2]
    p = _Plan(
        causal=causal, window=window, prefix=prefix,
        scale=float(scale if scale is not None else D ** -0.5),
        q_block=min(q_block or block_for(Sq), Sq),
        kv_block=min(kv_block or block_for(Skv), Skv),
        groups=H // KVH, interpret=interpret,
    )
    assert Sq % p.q_block == 0 and Skv % p.kv_block == 0

    def heads(x):  # [B, S, N, D] -> [B*N, S, D]
        return x.transpose(0, 2, 1, 3).reshape(-1, x.shape[1], D)

    with jax.named_scope(SCOPE):
        if causal and prefix is None:
            o = _attend(heads(q), heads(k), heads(v), p)
        else:  # the forward alone
            o = _forward(heads(q), heads(k), heads(v), p)[0]
        return o.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
