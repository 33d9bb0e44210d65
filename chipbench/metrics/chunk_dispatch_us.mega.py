"""Mean duration of the program's ``repro.grid.dispatch`` spans in the traced
recommend() calls: one chunk's host-to-device copy and program launch."""

from chipbench import program_spans


def read(record):
    tr = program_spans.of(record)
    mean = program_spans.span_mean_ns(tr, "repro.grid.dispatch") if tr else None
    return None if mean is None else mean / 1e3
