"""Operation and byte counts computed from shapes, and the chip's peaks.

The counts describe what an algorithm needs, not how the program computes
it, so a rewrite of a kernel is judged on the same yardstick.
"""

from __future__ import annotations

import json
import pathlib
from typing import Dict

PEAKS_FILE = pathlib.Path(__file__).resolve().parent.parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of one chip of ``device_kind``; an unknown kind is an error."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}")
    return table[device_kind]


def roofline_share(ops: float, nbytes: float, seconds: float, device_kind: str) -> float:
    """The least time the chip could take for the work, over ``seconds``."""
    p = peaks(device_kind)
    return max(ops / p["flops_per_s"], nbytes / p["hbm_bytes_per_s"]) / seconds
