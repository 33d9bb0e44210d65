"""Share of the attention layer's device time in the traced training steps
that the flash kernel takes: the program's ``repro.attention.flash`` scope
over it and ``repro.attention`` together (chipbench/op_scopes.py).  0 where
attention ran without the kernel; ``None`` where no attention scope is in
the trace."""

from chipbench import op_scopes

FLASH = "repro.attention.flash"
SCOPES = ("repro.attention", FLASH)


def read(record):
    times = op_scopes.of(record)
    if not times:
        return None
    whole = sum(times.get(s, 0.0) for s in SCOPES)
    if not whole:
        return None
    return 100.0 * times.get(FLASH, 0.0) / whole
