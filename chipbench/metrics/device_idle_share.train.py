"""Idle share of the device in the traced training steps."""

from chipbench.readers import device_idle_share as read  # noqa: F401
