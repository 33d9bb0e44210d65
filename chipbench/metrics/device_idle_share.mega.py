"""Idle share of the device in the traced recommend() calls (mega cell)."""

from chipbench.readers import device_idle_share as read  # noqa: F401
