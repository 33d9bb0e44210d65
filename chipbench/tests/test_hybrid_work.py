"""The hybrid training step's operation count, and the recurrence's
operations and bytes, against a hand count."""

import json
import pathlib

from chipbench.tests import tiny_hybrid
from chipbench.work.train_hybrid import matmul_params, ssd_work, step_flops

ROOT = pathlib.Path(__file__).resolve().parents[2]


def test_counts_by_hand_at_the_tiny_size():
    c = dict(tiny_hybrid.SIZES, mamba_n_groups=1)
    # MLP 3 x 64 x 128 = 24576 in each layer; Mamba-2: in_proj 64 x (64 + 80
    # + 4) = 9472, out_proj 64 x 64 = 4096; attention: q, k, v 64 x (4 + 2 +
    # 2) x 16 = 8192, o 4096; 3 Mamba-2 layers, 1 attention, head 64 x 512
    assert matmul_params(c) == 3 * (9472 + 4096 + 24576) + (8192 + 4096 + 24576) + 32768
    # 3 layers x 4 rows x 32 tokens; 4 heads x 4 x 16 x 8 = 2048 a token
    # forward, 3x with the backward; forward bytes 4 x (2 x 64 + 4 + 2 x 8)
    assert ssd_work(c, 4, 32) == {"ops": 3 * 2048 * 384.0, "bytes": 3 * 592 * 384.0}
    # 6 x 184064 x 128 + causal attention 6 x 1 x 4 x 16 x 32 x 33 x 4
    assert step_flops(c, 4, 32) == 6 * 184064 * 128 + 1622016 + 3 * 2048 * 384


def test_counts_of_the_cell():
    c = json.loads((ROOT / "chipbench/configs/granite-4.0-h-micro.json").read_text())
    assert matmul_params(c) == 951_713_792  # 9 Mamba-2 layers, 1 attention, head
    w = ssd_work(c, 4, 2048)
    assert w["ops"] == 3 * 4 * 64 * 64 * 128 * 9 * 8192
    assert 4.74e13 < step_flops(c, 4, 2048) < 4.75e13  # 0.24 s at 197 TFLOP/s
    # bytes bind: 7.5 GB at 819 GB/s is 9.2 ms, the operations 2.4 ms
    assert w["bytes"] / 819e9 > w["ops"] / 197e12
