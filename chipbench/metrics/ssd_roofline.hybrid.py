"""The Mamba-2 SSD's share of its roofline in the traced training steps:
the least time the chip needs for the recurrence's operations and bytes
(chipbench/work/train_hybrid.py ``ssd_work``, bytes bind) over the device
time of the program's ``repro.mamba2.ssd`` scope (chipbench/op_scopes.py)."""

from chipbench import op_scopes
from chipbench.work import roofline_share

SCOPE = "repro.mamba2.ssd"


def read(record):
    times = op_scopes.of(record)
    if not times or not times.get(SCOPE):
        return None
    n, w = times[op_scopes.STEPS], record["ssd_work_per_step"]
    return 100.0 * roofline_share(n * w["ops"], n * w["bytes"], times[SCOPE] / 1e9,
                                  record["device_kind"])
