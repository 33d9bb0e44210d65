"""Share of the device's busy time in the traced training steps spent in the
attention layer: operations of the program's ``repro.attention`` scope and
of its flash kernel's ``repro.attention.flash`` (chipbench/op_scopes.py)."""

from chipbench import op_scopes

SCOPES = ("repro.attention", "repro.attention.flash")


def read(record):
    times = op_scopes.of(record)
    if not times or not times.get(op_scopes.BUSY):
        return None
    spent = sum(times.get(s, 0.0) for s in SCOPES)
    if not spent:
        return None
    return 100.0 * spent / times[op_scopes.BUSY]
