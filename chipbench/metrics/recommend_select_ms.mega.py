"""Mean duration of the program's ``repro.recommend.select`` spans in the
traced recommend() calls: the top-k and the winners' re-score after the grid
is scored."""

from chipbench import program_spans


def read(record):
    tr = program_spans.of(record)
    mean = program_spans.span_mean_ns(tr, "repro.recommend.select") if tr else None
    return None if mean is None else mean / 1e6
