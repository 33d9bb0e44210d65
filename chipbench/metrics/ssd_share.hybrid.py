"""Share of the device's busy time in the traced training steps spent in
operations of the program's ``repro.mamba2.ssd`` scope (chipbench/op_scopes.py)."""

from chipbench import op_scopes

SCOPE = "repro.mamba2.ssd"


def read(record):
    times = op_scopes.of(record)
    if not times or not times.get(SCOPE) or not times.get(op_scopes.BUSY):
        return None
    return 100.0 * times[SCOPE] / times[op_scopes.BUSY]
