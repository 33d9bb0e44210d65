"""repro.train — step builders and the fault-tolerant trainer."""

from .step import StepBundle, make_serve_bundle, make_train_bundle  # noqa: F401
from .trainer import Trainer, TrainerConfig  # noqa: F401
