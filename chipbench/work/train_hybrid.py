"""Model operations of one training step of a granite-4.0-h hybrid (Mamba-2
mixers beside attention, a dense SwiGLU MLP in every layer), and the
operations and bytes of its state-space recurrence, whatever implements
them.

Per token, the forward and backward passes need 6 operations for each
matrix-product weight the token uses: the Mamba-2 ``in_proj`` and
``out_proj``, the attention projections, the MLP's three matrices and the
output head over the vocabulary.  Causal attention adds, per attention
layer and row, 6 x heads x head_dim x S(S+1), as ``work/train.py`` counts
it.  The recurrence ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``,
``y_t = S_t C_t`` needs 4 x head_dim x d_state operations a head and token
forward (the update's multiply-add and the read-out's), 3 times that with
the backward.  The conv, the norms, the gates, the embedding lookup and the
softmaxes count nothing; nor do recomputed forward passes, or the chunked
form's masked upper triangle.

The recurrence's bytes are what it has to move at least: forward it reads
x, dt, B and C and writes y (float32, as the configuration states), and
the backward reads those and dy and writes their gradients, twice the
forward's traffic.  The state stays on chip in both.
"""

from __future__ import annotations

from typing import Dict


def _sizes(config: dict):
    layers = config["layer_types"]
    n_attn = sum(t == "attention" for t in layers)
    return len(layers) - n_attn, n_attn


def matmul_params(config: dict) -> int:
    """Matrix-product weights one token uses, output head included."""
    D, H, KV = config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"]
    hd = D // H
    F, V = config["shared_intermediate_size"], config["vocab_size"]
    hs, P = config["mamba_n_heads"], config["mamba_d_head"]
    di = hs * P
    conv_dim = di + 2 * config["mamba_n_groups"] * config["mamba_d_state"]
    n_mamba, n_attn = _sizes(config)
    mlp = 3 * D * F
    mamba = D * (di + conv_dim + hs) + di * D + mlp
    attention = D * (H + 2 * KV) * hd + H * hd * D + mlp
    return n_mamba * mamba + n_attn * attention + D * V


def ssd_work(config: dict, batch: int, seq: int) -> Dict[str, float]:
    """Operations and bytes of one step's recurrences, forward and backward."""
    hs, P, N = config["mamba_n_heads"], config["mamba_d_head"], config["mamba_d_state"]
    G = config["mamba_n_groups"]
    n_mamba, _ = _sizes(config)
    tokens = n_mamba * batch * seq
    forward_bytes = 4 * (2 * hs * P + hs + 2 * G * N)
    return {"ops": float(3 * 4 * hs * P * N * tokens),
            "bytes": float(3 * forward_bytes * tokens)}


def step_flops(config: dict, batch: int, seq: int) -> float:
    """Operations of one step on ``batch`` rows of ``seq`` tokens."""
    D, H = config["hidden_size"], config["num_attention_heads"]
    _, n_attn = _sizes(config)
    dense = 6 * matmul_params(config) * batch * seq
    attention = 6 * n_attn * H * (D // H) * seq * (seq + 1) * batch
    return float(dense + attention + ssd_work(config, batch, seq)["ops"])
