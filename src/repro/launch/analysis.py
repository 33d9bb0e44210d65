"""Compiled-artifact analysis: collective-byte extraction from HLO text and
roofline-term computation. Pure text/number processing — safe to import
anywhere (no jax device-state side effects).

Hardware model (TPU v5e-class, per assignment):
  peak bf16 compute: 197 TFLOP/s per chip
  HBM bandwidth:     819 GB/s per chip
  ICI link:          ~50 GB/s per link
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional

__all__ = ["HW", "CollectiveOp", "parse_collectives", "roofline_terms"]

PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9

HW = {"peak_flops": PEAK_FLOPS, "hbm_bw": HBM_BW, "link_bw": LINK_BW}

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLL_RE = re.compile(
    r"=\s*(?P<outs>\(?[a-z0-9]+\[[0-9,]*\][^=]*?)\s*"
    r"(?P<op>all-reduce-start|all-reduce|all-gather-start|all-gather|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute)\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")


@dataclasses.dataclass
class CollectiveOp:
    op: str
    out_bytes: int
    group_size: int
    wire_bytes: float  # estimated bytes on the wire per participating device
    line: str = ""


def _line_group_size(line: str, default: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return default


def _shapes_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _wire_bytes(op: str, out_bytes: int, g: int) -> float:
    """Ring-algorithm wire-byte estimates per device."""
    if g <= 1:
        return 0.0
    frac = (g - 1) / g
    if op.startswith("all-reduce"):
        return 2.0 * out_bytes * frac
    if op.startswith("all-gather"):
        return out_bytes * frac  # out is the gathered size
    if op == "reduce-scatter":
        return out_bytes * (g - 1)  # out is the per-shard size
    if op == "all-to-all":
        return out_bytes * frac
    if op.startswith("collective-permute"):
        return float(out_bytes)
    return float(out_bytes)


def parse_collectives(hlo_text: str, default_group: int = 1) -> List[CollectiveOp]:
    ops: List[CollectiveOp] = []
    for line in hlo_text.splitlines():
        if "replica_groups" not in line and "all-" not in line and "collective-permute" not in line:
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group("op").replace("-start", "")
        out_bytes = _shapes_bytes(m.group("outs"))
        g = _line_group_size(line, default_group)
        ops.append(CollectiveOp(op, out_bytes, g, _wire_bytes(op, out_bytes, g),
                                line.strip()[:160]))
    return ops


def roofline_terms(
    per_device_flops: float,
    per_device_bytes: float,
    per_device_wire_bytes: float,
    model_flops: Optional[float] = None,
    n_chips: int = 256,
) -> Dict[str, float]:
    """All inputs are per-device quantities from the SPMD executable."""
    t_compute = per_device_flops / PEAK_FLOPS
    t_memory = per_device_bytes / HBM_BW
    t_coll = per_device_wire_bytes / LINK_BW
    dom = max(
        ("compute", t_compute), ("memory", t_memory), ("collective", t_coll),
        key=lambda kv: kv[1],
    )[0]
    out = {
        "compute_s": t_compute,
        "memory_s": t_memory,
        "collective_s": t_coll,
        "bottleneck": dom,
        "per_device_flops": per_device_flops,
        "per_device_bytes": per_device_bytes,
        "per_device_wire_bytes": per_device_wire_bytes,
        "n_chips": n_chips,
    }
    if model_flops is not None:
        hlo_global = per_device_flops * n_chips
        out["model_flops"] = model_flops
        out["useful_flops_ratio"] = model_flops / hlo_global if hlo_global else 0.0
        # roofline fraction: useful work / (time-bound * peak)
        t_bound = max(t_compute, t_memory, t_coll)
        out["roofline_fraction"] = (
            (model_flops / n_chips / PEAK_FLOPS) / t_bound if t_bound > 0 else 0.0
        )
    return out
