"""Mean duration of the program's ``repro.grid.assemble`` spans in the traced
recommend() calls: filling one chunk's float32 buffer on the host."""

from chipbench import program_spans


def read(record):
    tr = program_spans.of(record)
    mean = program_spans.span_mean_ns(tr, "repro.grid.assemble") if tr else None
    return None if mean is None else mean / 1e3
