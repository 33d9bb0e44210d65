"""Work of scoring rows through a tree ensemble, whatever implements it.

Bytes: the float32 feature matrix read once, one float32 score written per
row, and each real node's five table entries (feature, threshold, left,
right, value; 4 bytes each) read once.  Operations: per row, per tree, per
level of the deepest path, one feature select, one compare and one select;
then one add per tree.  Padding of tables or rows, chunking and the one-hot
formulation add nothing here.

At the paper's sizes bytes bind: 10^6 rows of 11 features take about 48 MB
(59 us at 819 GB/s), while their 1.9 x 10^9 operations take 10 us at the
197 TFLOP/s peak.
"""

from __future__ import annotations

from typing import Tuple


def descent_work(rows: int, features: int, trees: int, max_depth: int,
                 nodes: int) -> Tuple[float, float]:
    """(operations, bytes) of scoring ``rows`` rows of ``features`` float32
    features through ``trees`` trees of depth ``max_depth`` holding
    ``nodes`` real nodes in all."""
    ops = rows * trees * (3 * max_depth + 1)
    nbytes = 4 * rows * features + 4 * rows + 5 * 4 * nodes
    return float(ops), float(nbytes)
