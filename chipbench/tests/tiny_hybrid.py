"""The hybrid training cell at a size a CPU test run holds: one period of 4
layers with attention at 2, d_model 64, 4 query and 2 key/value heads of
16, 4 Mamba-2 heads of 16 with d_state 8 in SSD chunks of 8, an MLP of 128,
512 ids, rows of 32 tokens."""

import copy
import pathlib
import tempfile

from chipbench import run as harness

CELL = "train-granite-h-micro-tmpfs"
SIZES = {"num_hidden_layers": 4, "hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "intermediate_size": 128,
         "shared_intermediate_size": 128, "vocab_size": 512, "mamba_n_heads": 4,
         "mamba_d_head": 16, "mamba_d_state": 8, "mamba_expand": 1, "mamba_chunk_size": 8,
         "layer_types": ["mamba", "mamba", "attention", "mamba"]}
# The program's registered configuration, cut to the same sizes.
PROGRAM = dict(n_layers=4, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
               vocab_size=512, attn_period=4, attn_phase=2, expand=1, ssm_heads=4,
               ssm_head_dim=16, d_state=8, ssm_chunk=8, q_chunk=16, xent_chunk=64)
# Sound runs on the CPU read up to loss 2.8e-06, grad 5.5e-03, change
# 3.7e-03 and ssd grad 7.0e-02 over 6 seeds (the model is small, so a few
# bfloat16 rounding steps turn a leaf more than at the chip's size); the
# float8 control at least grad 2.5e-02, the state reset at every chunk ssd
# grad 0.85, D x or silu(z) left out grad 0.74.  The program's chunked SSD
# against the recurrence reads at most 5.6e-06 over 12 seeds (float32 on the
# CPU); the state carried in bfloat16 at least 3.3e-03, the SSD's products
# with bfloat16 operands 3.6e-03.
LIMITS = {"loss_gap": 3e-5, "grad_gap": 1.5e-2, "change_gap": 1e-2, "ssd_grad_gap": 0.25,
          "ssd_gap": 3e-4}
TRAFFIC = {"seq": 32, "records": 2048, "num_steps": 512, "limits": LIMITS}
CPU = {"platform": "cpu", "kind": "cpu", "count": 1, "memory_peak_bytes": 0}


def config(base: dict) -> dict:
    c = copy.deepcopy(base)
    c.update(SIZES)
    c["as_run"]["period"] = 4
    return c


def cell(monkeypatch, bench=None):
    """The tiny cell; the program's registered configuration is cut to the
    same sizes for the test (``monkeypatch`` undoes it)."""
    from repro.configs import archs

    tiny = archs.ARCHS["granite-4.0-h-micro"].replace(**PROGRAM)
    monkeypatch.setitem(archs.ARCHS, "granite-4.0-h-micro", tiny)
    c = harness.resolve(CELL, bench=bench)
    c.config = config(c.config)
    c.traffic = dict(c.traffic, **TRAFFIC)
    return c


def run(monkeypatch, seed: int = 2**31 + 21, seconds: float = 1.0, control: bool = False):
    """One whole run of the tiny cell on the CPU: (cell, record, result).
    Its dataset lives in a directory of its own, so that runs in parallel
    test processes do not remove each other's."""
    c = cell(monkeypatch)
    monkeypatch.setattr(c.driver, "WORK_DIR", pathlib.Path(tempfile.mkdtemp(prefix="hybrid-")))
    r = harness.Run(seed=seed, seconds=seconds, trace=False, control=control)
    record = c.driver.run(c, r)
    return c, record, harness.finish(c, record, False, CPU)
