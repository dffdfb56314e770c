"""Model FLOPs of one Qwen3 training step (forward + backward), from the
configuration's sizes: 6 × matmul parameters × tokens, plus the
attention products that have no parameters.  Recomputation is not
counted; the tied head counts once.
"""
from __future__ import annotations

from bench.counts import attention


def train_flops(c: dict, B: int, S: int) -> float:
    E, H, K = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    D, F, L = c["head_dim"], c["intermediate_size"], c["num_hidden_layers"]
    per_layer = E * H * D * 2 + E * K * D * 2 + 3 * E * F
    matmul_params = L * per_layer + E * c["vocab_size"]
    return 6.0 * matmul_params * B * S \
        + L * attention.flops(B, S, H, D, backward=True)
