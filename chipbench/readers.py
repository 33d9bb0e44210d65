"""Reductions that several metrics share; each metric's own file under
``metrics/`` names one of them, so that cells which report different
end-to-end metrics keep separate names for the same quantity."""

from __future__ import annotations

from typing import Optional

from chipbench import trace_reduce


def device_idle_share(record) -> Optional[float]:
    """Share of the traced slice with no operation on the device."""
    tr = record.get("trace")
    share = trace_reduce.idle_share(tr) if tr is not None else None
    return None if share is None else 100.0 * share
