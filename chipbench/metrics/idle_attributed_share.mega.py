"""Share of the device's idle time inside the traced recommend() calls
(``chipbench.recommend`` spans) that some span of the program (``repro.``)
covers, so that a phase of the host's work is named for it."""

from chipbench import program_spans

CALL = "chipbench.recommend"


def read(record):
    tr = program_spans.of(record)
    if tr is None:
        return None
    covered = program_spans.idle_in_spans_ns(tr, CALL, program_spans.PREFIX)
    idle = program_spans.idle_in_spans_ns(tr, CALL)
    return None if covered is None or not idle else 100.0 * covered / idle
