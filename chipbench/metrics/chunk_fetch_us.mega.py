"""Mean duration of the program's ``repro.grid.fetch`` spans in the traced
recommend() calls: the wait for one chunk's scores and their copy back."""

from chipbench import program_spans


def read(record):
    tr = program_spans.of(record)
    mean = program_spans.span_mean_ns(tr, "repro.grid.fetch") if tr else None
    return None if mean is None else mean / 1e3
