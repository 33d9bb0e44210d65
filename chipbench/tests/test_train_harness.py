"""The training cell resolves from BENCHMARK.json, and its window and
readers reduce recorded numbers as written."""

import pytest

from chipbench import run as harness
from chipbench import trace_reduce as tr
from chipbench.drivers import train as drv

CELL = "train-granite-moe-tmpfs"


def test_training_cell_resolves_every_file():
    cell = harness.resolve(CELL)
    assert cell.chips == 1 and cell.traffic["driver"] == "train"
    assert callable(cell.driver.run)
    assert cell.config["name"] == "granite-moe-1b-a400m"
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "train_tokens_per_s"}
    assert set(cell.readers) == {"setup_s", "train_tokens_per_s", "train_step_ms_p50",
                                 "data_wait_share.train", "device_idle_share.train",
                                 "train_mfu"}
    assert {m["moves"] for m in cell.per_layer} == {"train_tokens_per_s"}
    assert set(cell.traffic["limits"]) == set(cell.traffic["limits_why"])


def test_window_leaves_out_the_warmup_and_ends_with_the_step_past_the_seconds():
    # steps 1-4 warm up (step 1 compiles); the window opens at 11.5 as step 5
    # starts; steps 5, 6, 7 end at 12.0, 12.5, 13.0, the first >= 1.2 s in
    starts = [0.0, 10.0, 10.5, 11.0, 11.5, 12.0, 12.5, 13.0, 13.5]
    assert drv.window_steps(starts, 4, 1.2) == (3, 1.5)
    assert drv.window_steps(starts[:7], 4, 1.2) is None  # still open
    assert drv.window_steps(starts, 4, 1.2, min_steps=4) == (4, 2.0)
    read = harness.resolve(CELL).readers["train_tokens_per_s"]
    assert read({"train_tokens": 3 * 4 * 2048, "window_s": 1.5}) == 3 * 8192 / 1.5
    assert read({}) is None


def test_median_step_is_read_in_ms():
    read = harness.resolve(CELL).readers["train_step_ms_p50"]
    assert read({"step_s": [0.3, 2.0, 0.31]}) == pytest.approx(310.0)
    assert read({"step_s": []}) is None


# One chip, window [0, 100] ns: steps [0, 40] and [40, 100]; the fetch of
# the second step [36, 40] and its copy [40, 44], the first copy [0, 3];
# ops busy [5, 35] and [45, 95]; a step span [100, 140] lies past the window.
HAND = tr.Trace(
    ops={"/device:TPU:0": [(5, 35, "fusion"), (45, 95, "fusion")]},
    programs={"/device:TPU:0": [(5, 35, "jit_step"), (45, 95, "jit_step")]},
    spans=[(0, 100, tr.WINDOW_SPAN), (0, 40, drv.SPAN_STEP), (40, 100, drv.SPAN_STEP),
           (100, 140, drv.SPAN_STEP), (0, 3, drv.SPAN_BATCH), (36, 40, drv.SPAN_FETCH),
           (40, 44, drv.SPAN_BATCH)],
)


def test_trace_readers_by_hand():
    readers = harness.resolve(CELL).readers
    record = {"trace": HAND, "flops_per_step": 197e12 * 1e-8, "device_kind": "TPU v5 lite"}
    # waits 3 + 4 + 4 = 11 of 100
    assert readers["data_wait_share.train"](record) == pytest.approx(11.0)
    # busy 30 + 50 of 100
    assert readers["device_idle_share.train"](record) == pytest.approx(20.0)
    # 2 steps of 10 ns at the peak in 100 ns
    assert readers["train_mfu"](record) == pytest.approx(20.0)
    assert all(readers[m]({}) is None for m in
               ("data_wait_share.train", "device_idle_share.train", "train_mfu"))
