"""Operation and byte counts against hand counts, and the peaks table."""

import numpy as np
import pytest

from chipbench import reference
from chipbench.work import peaks, roofline_share
from chipbench.work.descent import descent_work


def test_descent_work_by_hand():
    # 2 rows of 3 features, 4 trees of depth 2 with 13 real nodes in all:
    # ops 2 * 4 * (3 * 2 + 1) = 56; bytes 4*2*3 + 4*2 + 20*13 = 24 + 8 + 260
    assert descent_work(2, 3, 4, 2, 13) == (56.0, 292.0)


def test_descent_work_at_the_mega_grid():
    ops, nbytes = descent_work(10**6, 11, 100, 6, 100 * 127)
    assert ops == 1.9e9
    assert nbytes == 4 * 11e6 + 4e6 + 20 * 12700


def test_real_nodes_ignore_padding():
    # tree 0: root 0 -> leaves 1, 2; tree 1: a lone leaf; both padded to 4
    # nodes with self-looping padding
    ens = reference.Ensemble(
        feature=np.array([[0, -1, -1, -1], [-1, -1, -1, -1]]),
        threshold=np.zeros((2, 4)), left=np.array([[1, 1, 2, 3], [0, 1, 2, 3]]),
        right=np.array([[2, 1, 2, 3], [0, 1, 2, 3]]), value=np.zeros((2, 4)),
        max_depth=1, base=0.0, scale=1.0)
    assert ens.real_nodes() == 4


def test_peaks_of_v5e():
    p = peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks("cpu")
    with pytest.raises(KeyError):
        roofline_share(1.0, 1.0, 1.0, "TPU v99")


def test_roofline_share_takes_the_binding_bound():
    # 819 MB at 819 GB/s is 1 ms; 1 GFLOP at 197 TFLOP/s is far less
    assert roofline_share(1e9, 819e6, 2e-3, "TPU v5 lite") == pytest.approx(0.5)
    assert roofline_share(197e9, 0.0, 4e-3, "TPU v5 lite") == pytest.approx(0.25)
