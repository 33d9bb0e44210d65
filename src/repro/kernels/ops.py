"""Scoring a ``core.ensemble_base.PackedEnsemble`` with the Pallas kernel.

The kernel compiles for the TPU.  ``interpret=True`` runs the same kernel
body in the Pallas interpreter instead (exact semantics, any backend); only
a caller that asks for it gets it, as the CPU tests do.
"""

from __future__ import annotations

import jax.numpy as jnp

from .gbt_predict import gbt_predict as _gbt


def gbt_predict_op(X, ensemble, *, row_block=None, interpret=False):
    """Score ``X`` [N, F] with a ``core.ensemble_base.PackedEnsemble``."""
    return _gbt(
        jnp.asarray(X, jnp.float32),
        ensemble.feature, ensemble.threshold, ensemble.left, ensemble.right,
        ensemble.value, max_depth=ensemble.max_depth,
        base_score=ensemble.base_score, scale=ensemble.scale,
        row_block=row_block, interpret=interpret,
    )
