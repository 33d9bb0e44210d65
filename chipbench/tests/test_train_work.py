"""The training step's operation count against a hand count."""

import json
import pathlib

from chipbench.tests import tiny_train
from chipbench.work.train import active_matmul_params, step_flops

ROOT = pathlib.Path(__file__).resolve().parents[2]


def test_step_flops_by_hand_at_the_tiny_size():
    c = dict(tiny_train.SIZES)
    # per layer: q, k, v 64 x (4 + 2 + 2) x 16 = 8192; o 4 x 16 x 64 = 4096;
    # router 64 x 8 = 512; 2 experts x 3 x 64 x 32 = 12288 -> 25088 x 2 layers;
    # output head 64 x 512 = 32768
    assert active_matmul_params(c) == 2 * 25088 + 32768 == 82944
    # 6 x 82944 x (4 x 32) + causal attention 6 x 2 x 4 x 16 x 32 x 33 x 4
    assert step_flops(c, 4, 32) == 6 * 82944 * 128 + 3244032 == 66945024


def test_step_flops_of_the_cell():
    c = json.loads((ROOT / "chipbench/configs/granite-moe-1b-a400m.json").read_text())
    assert active_matmul_params(c) == 16 * 15761408 + 1024 * 49155
    assert 1.65e13 < step_flops(c, 4, 2048) < 1.66e13  # 84 ms at 197 TFLOP/s
