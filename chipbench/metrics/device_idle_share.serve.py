"""Idle share of the device in the traced slice (steady cell)."""

from chipbench.readers import device_idle_share as read  # noqa: F401
