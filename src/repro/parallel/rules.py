"""Per-(arch × shape × mesh) sharding-rule selection.

Policy:

- batch   -> all DP axes ("pod","data") when the global batch divides; else None
- heads / kv_heads -> "model" when divisible by TP (heads_tp archs)
- act_seq -> "model" for seq_tp archs on train/prefill (sequence parallelism)
- kv_seq  -> decode-cache sequence sharding when kv heads are unshardable;
             spreads over idle DP axes too when batch == 1 (long-context)
- mlp     -> "model" (TP); over ("data","model") for batch-1 SSM decode
- expert  -> "model" (EP)
- vocab   -> "model"
"""

from __future__ import annotations

from typing import Tuple

from ..models.config import ModelConfig
from .spec import Rules

__all__ = ["make_rules", "mesh_dp_axes"]


def mesh_dp_axes(multi_pod: bool) -> Tuple[str, ...]:
    return ("pod", "data") if multi_pod else ("data",)


def make_rules(
    cfg: ModelConfig,
    kind: str,  # train | prefill | decode
    global_batch: int,
    multi_pod: bool = False,
    tp: int = 16,
    dp: int = 16,
) -> Rules:
    dp_axes = mesh_dp_axes(multi_pod)
    n_dp = dp * (2 if multi_pod else 1)
    batch_axes = dp_axes if global_batch % n_dp == 0 else None

    heads_ok = cfg.n_heads > 0 and cfg.n_heads % tp == 0
    kv_ok = cfg.n_kv_heads > 0 and cfg.n_kv_heads % tp == 0
    seq_tp = cfg.attn_mode == "seq_tp" and kind in ("train", "prefill")

    r = dict(
        batch=batch_axes,
        vocab="model",
        embed=None,
        mlp="model",
        expert="model",
        layers=None,
        state=None,
        heads=("model" if heads_ok and not seq_tp else None),
        kv_heads=("model" if kv_ok and not seq_tp else None),
        act_seq=("model" if seq_tp else None),
        kv_seq=None,
        capacity=None,
        frames=None,
        conv=None,
    )

    if kind == "decode":
        # cache sequence sharding when kv heads can't use the model axis
        if not kv_ok:
            r["kv_seq"] = ("data", "model") if batch_axes is None else "model"
        if batch_axes is None and cfg.family == "ssm":
            # batch-1 SSM decode: spread channels over every axis
            r["mlp"] = (("pod", "data", "model") if multi_pod else ("data", "model"))
    return Rules.make(**r)
