"""Batched tree-ensemble inference kernel (the mega-grid scorer behind
``autotune.recommend``: 10^4-10^6 candidate configurations per call).

TPU adaptation: tree descent is gather-heavy; TPUs prefer dense math.  Each
descent level is ONE one-hot matmul on the MXU: the tree's node table
``[16, n_pad]`` times the node one-hot ``[n_pad, rows]`` (nodes on sublanes,
candidate rows on lanes) gives every node attribute of every row at once.
Rows stay on lanes throughout, so the per-row state (node index, feature
value, score) is a lane-dense ``[1, rows]`` vector and so is the output.

Exactness: the MXU multiplies bf16.  Each f32 table entry is stored as three
bf16 parts whose f32 sum is the entry exactly (``_split3``); a one-hot column
selects exactly one node, so the f32-accumulated product returns the f32
table entry bit for bit, and the descent makes the same comparisons as the
gather descent in ``core/ensemble_base.py``.

Layout: node tables are padded to a multiple of 128 lanes (padding nodes are
self-looping zero-value leaves, as in ``pack_trees``).  The grid is
``(row blocks, trees)``: rows ``parallel``, trees ``arbitrary``.  Each step
brings in one tree's ``[16, n_pad]`` bf16 table, and the output block stays
resident across the tree axis as the per-row accumulator.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Table rows: (feature, threshold, left, right, value) x 3 bf16 parts, padded
# to one bf16 sublane tile.
_TABLE_ROWS = 16
# Largest one-hot block, in elements: [2048 nodes, 256 rows] is 1 MiB in bf16
# (2 MiB for its int32 iota), well inside the default scoped VMEM.
_ONEHOT_ELEMS = 1 << 19
_MAX_ROW_BLOCK = 2048


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _bf16_head(x):
    """``x`` with the low 16 bits of its f32 pattern cleared: its top 8
    significant bits, a value bf16 holds exactly."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _split3(v):
    """f32 -> three bf16 parts with ``(hi + mid) + lo == v`` exactly in f32:
    each part takes the next 8 significant bits of the 24.  The parts are
    cut from the bit pattern, not rounded through bf16: XLA may keep excess
    precision across an f32 -> bf16 -> f32 round trip inside a fusion (the
    TPU backend does), which would leave the lower parts zero."""
    hi = _bf16_head(v)
    r = v - hi
    mid = _bf16_head(r)
    return (hi.astype(jnp.bfloat16), mid.astype(jnp.bfloat16),
            (r - mid).astype(jnp.bfloat16))


def node_tables(feature, threshold, left, right, value):
    """[T, nodes] tree arrays -> the kernel's [T, 16, n_pad] bf16 tables."""
    T, n = feature.shape
    n_pad = _round_up(n, 128)
    self_loop = jnp.broadcast_to(jnp.arange(n, n_pad, dtype=jnp.float32), (T, n_pad - n))

    def padded(a, fill):
        a = a.astype(jnp.float32)
        fill = jnp.broadcast_to(jnp.asarray(fill, jnp.float32), (T, n_pad - n))
        return jnp.concatenate([a, fill], axis=1)

    fields = (padded(feature, -1.0), padded(threshold, 0.0),
              padded(left, self_loop), padded(right, self_loop),
              padded(value, 0.0))
    parts = [p for f in fields for p in _split3(f)]
    parts += [jnp.zeros((T, n_pad), jnp.bfloat16)] * (_TABLE_ROWS - len(parts))
    return jnp.stack(parts, axis=1)


def _field(g, k: int):
    """Field ``k`` of the gathered [16, rows] rows, re-assembled from its
    three parts (summed in the order ``_split3`` guarantees exact)."""
    j = 3 * k
    return (g[j:j + 1] + g[j + 1:j + 2]) + g[j + 2:j + 3]


def _gbt_kernel(xt_ref, tab_ref, o_ref, *, max_depth: int):
    xt = xt_ref[...]  # [F, rows] f32
    tab = tab_ref[0]  # [16, n_pad] bf16
    F, rows = xt.shape
    n_pad = tab.shape[1]
    node_iota = jax.lax.broadcasted_iota(jnp.int32, (n_pad, rows), 0)
    feat_iota = jax.lax.broadcasted_iota(jnp.int32, (F, rows), 0)

    def gather(idx):  # [1, rows] node index -> [16, rows] node attributes
        oh = (node_iota == idx).astype(jnp.float32).astype(jnp.bfloat16)
        return jnp.dot(tab, oh, preferred_element_type=jnp.float32)

    idx = jnp.zeros((1, rows), jnp.int32)
    for _ in range(max_depth + 1):
        g = gather(idx)
        feat = _field(g, 0)  # -1 at leaves
        fi = jnp.maximum(feat, 0.0).astype(jnp.int32)
        fx = jnp.sum(jnp.where(feat_iota == fi, xt, 0.0), axis=0, keepdims=True)
        nxt = jnp.where(fx <= _field(g, 1), _field(g, 2), _field(g, 3))
        idx = jnp.where(feat < 0.0, idx, nxt.astype(jnp.int32))
    val = _field(gather(idx), 4)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += val


def _row_block(n_pad: int, n_rows: int, row_block) -> int:
    cap = max(128, min(_MAX_ROW_BLOCK, _ONEHOT_ELEMS // n_pad) // 128 * 128)
    want = _round_up(row_block, 128) if row_block else cap
    return min(want, cap, _round_up(n_rows, 128))


@functools.partial(jax.jit, static_argnames=("max_depth", "row_block", "interpret"))
def gbt_predict(
    X, feature, threshold, left, right, value, *,
    max_depth: int, base_score=0.0, scale=1.0,
    row_block=None, interpret: bool = False,
):
    """X: [N, F] f32; tree tables: [T, nodes]. Returns [N] f32 predictions,
    ``base_score + scale * sum_t tree_t(X)``.

    ``row_block`` (rounded up to 128 lanes) defaults to the largest block
    whose one-hot fits the VMEM budget; ``interpret=True`` runs the kernel
    in the Pallas interpreter (the CPU tests)."""
    X = jnp.asarray(X, jnp.float32)
    N, F = X.shape
    tables = node_tables(feature, threshold, left, right, value)
    T, _, n_pad = tables.shape
    rb = _row_block(n_pad, N, row_block)
    n_rows = _round_up(N, rb)
    f_pad = _round_up(F, 8)
    xt = jnp.pad(X, ((0, n_rows - N), (0, f_pad - F))).T  # rows on lanes

    raw = pl.pallas_call(
        functools.partial(_gbt_kernel, max_depth=max_depth),
        grid=(n_rows // rb, T),
        in_specs=[
            pl.BlockSpec((f_pad, rb), lambda ri, ti: (0, ri)),
            pl.BlockSpec((1, _TABLE_ROWS, n_pad), lambda ri, ti: (ti, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rb), lambda ri, ti: (0, ri)),
        out_shape=jax.ShapeDtypeStruct((1, n_rows), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="gbt_predict",
    )(xt, tables)
    base = jnp.asarray(base_score, jnp.float32)
    return (base + jnp.asarray(scale, jnp.float32) * raw[0])[:N]
