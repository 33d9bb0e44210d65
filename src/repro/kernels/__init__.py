"""repro.kernels — Pallas TPU kernels (compiled on a TPU; the CPU tests ask
for interpret mode).

flash_attention: attention with its backward; ``attention_heads_tp`` runs it
                on a TPU for the calls ``models.common.flash_applies`` takes.
rmsnorm:        fused memory-bound norm.
gbt_predict:    the paper's hot path — batched ensemble inference for
                autotune sweeps, one-hot-matmul descent (gather-free).
"""

from .ops import gbt_predict_op  # noqa: F401
