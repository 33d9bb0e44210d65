"""Drivers: one per entry point that a cell's window drives."""

from typing import List


def check_fitted(cfg: dict, want_names, names, model) -> List[str]:
    """Where the fitted program departs from the configuration: its feature
    row and the model's hyperparameters.  Each departure is an error."""
    errors = []
    if tuple(names) != tuple(want_names):
        errors.append(f"program features {tuple(names)} differ from the configuration's")
    mc = getattr(model, "config", None)
    for k in ("n_estimators", "max_depth", "learning_rate", "subsample"):
        if getattr(mc, k, None) != cfg["model"][k]:
            errors.append(f"program model {k}={getattr(mc, k, None)} differs from "
                          f"the configuration's {cfg['model'][k]}")
    return errors
