"""Model operations of one training step of a decoder with top-k experts,
whatever implements it.

Per token, the forward and backward passes need 6 operations for each
matrix-product weight the token uses: the attention projections, the
router, its top-k experts (three SwiGLU matrices each) and the output head
over the published vocabulary.  Causal attention adds, per layer and row,
6 x heads x head_dim x S(S+1) for the score and value products over the
S(S+1)/2 query-key pairs.  The embedding lookup, norms and softmaxes count
nothing.  Recomputed forward passes, capacity padding, dropped assignments,
padded vocabulary rows and masked-out score blocks count nothing either:
this is what the model needs, not what a program spends.
"""

from __future__ import annotations


def active_matmul_params(config: dict) -> int:
    """Matrix-product weights one token uses, output head included."""
    D, H, KV = config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"]
    hd, F, E = config["head_dim"], config["intermediate_size"], config["num_local_experts"]
    K, L, V = config["num_experts_per_tok"], config["num_hidden_layers"], config["vocab_size"]
    per_layer = D * (H + 2 * KV) * hd + H * hd * D + D * E + K * 3 * D * F
    return L * per_layer + D * V


def step_flops(config: dict, batch: int, seq: int) -> float:
    """Operations of one step on ``batch`` rows of ``seq`` tokens."""
    L, H, hd = config["num_hidden_layers"], config["num_attention_heads"], config["head_dim"]
    dense = 6 * active_matmul_params(config) * batch * seq
    attention = 6 * L * H * hd * seq * (seq + 1) * batch
    return float(dense + attention)
