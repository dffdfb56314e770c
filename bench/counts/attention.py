"""Causal self-attention (GQA) over one sequence batch."""
from __future__ import annotations


def causal_pairs(S: int) -> int:
    """Query-key pairs a causal mask keeps: S (S + 1) / 2."""
    return S * (S + 1) // 2


def flops(B: int, S: int, H: int, D: int, *, backward: bool) -> float:
    """QK^T and PV over the causal pairs: 2 matmuls × 2 flops a
    multiply-add; the backward pass needs twice the forward's products
    (dP = dO V^T and dV = P^T dO, then dS Q and dS^T K)."""
    fwd = 2 * 2 * B * H * D * causal_pairs(S)
    return fwd * (3 if backward else 1)


def bytes_moved(B: int, S: int, H: int, K: int, D: int, *, elem: int = 2,
                backward: bool) -> float:
    """Forward: read q, k, v, write o (and a float32 row statistic).
    Backward: read q, k, v, o, dO and the row statistics, write dq, dk, dv."""
    q = B * S * H * D * elem
    kv = B * S * K * D * elem
    rows = B * H * S * 4
    fwd = q + 2 * kv + q + rows
    if not backward:
        return fwd
    bwd = 3 * q + 2 * kv + 2 * rows + q + 2 * kv
    return fwd + bwd
