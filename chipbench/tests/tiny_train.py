"""The training cell at a size a CPU test run holds: 2 layers, d_model 64,
4 query and 2 key/value heads of 16, 8 experts of 32 (padded to 16 rows, as
the program pads them), top-2, 512 ids, rows of 32 tokens."""

import copy

from chipbench import run as harness

CELL = "train-granite-moe-tmpfs"
SIZES = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 32,
         "num_local_experts": 8, "num_experts_per_tok": 2, "vocab_size": 512}
AS_RUN = {"expert_rows": 16, "vocab_rows": 512, "attention_scale": 0.25}
# At this size a leaf holds thousands of elements, not millions, so how many
# of them a 6e-5 update moves past a bfloat16 rounding step varies more:
# sound runs on the CPU read up to loss 3.1e-05, grad 2.8e-03, change
# 4.8e-03 over 10 seeds; the float8 control at least loss 1.9e-04, half the
# batch change 1.9e-02, top-1 routing grad 0.36.
LIMITS = {"loss_gap": 8e-5, "grad_gap": 1.5e-2, "change_gap": 1.5e-2}
TRAFFIC = {"seq": 32, "records": 2048, "num_steps": 512, "limits": LIMITS}
CPU = {"platform": "cpu", "kind": "cpu", "count": 1, "memory_peak_bytes": 0}


def config(base: dict) -> dict:
    c = copy.deepcopy(base)
    c.update(SIZES)
    c["as_run"].update(AS_RUN)
    return c


def cell(bench=None):
    c = harness.resolve(CELL, bench=bench)
    c.config = config(c.config)
    c.traffic = dict(c.traffic, **TRAFFIC)
    return c


def run(seed: int = 2**31 + 21, seconds: float = 1.0, trace: bool = False,
        control: bool = False):
    """One whole run of the tiny cell on the CPU: (cell, record, result)."""
    c = cell()
    r = harness.Run(seed=seed, seconds=seconds, trace=trace, control=control,
                    tracer=harness.Tracer(harness.WORK_DIR / "trace-test") if trace else None)
    record = c.driver.run(c, r)
    return c, record, harness.finish(c, record, False, CPU)
