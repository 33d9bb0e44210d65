"""repro.optim — AdamW, schedules, clipping."""

from .adamw import AdamWConfig, adamw_init_specs, adamw_update  # noqa: F401
from .schedule import cosine_schedule  # noqa: F401
