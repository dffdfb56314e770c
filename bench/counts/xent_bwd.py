"""Backward cross-entropy over a (padded) vocabulary, given the forward's
per-row log-sum-exp: the hidden rows' and the head's gradients."""
from __future__ import annotations


def flops(T: int, E: int, V: int) -> float:
    """dh = dlogits · Wᵀ and dW = hᵀ · dlogits, each a (T, E, V) product, 2
    flops a multiply-add; the logits are not recomputed."""
    return 4.0 * T * E * V


def bytes_moved(T: int, E: int, V: int, *, elem: int = 2) -> float:
    """Read the hidden rows, the head, the per-row log-sum-exp and the
    labels (4 bytes each) once; write dh and dW once."""
    return 2 * T * E * elem + 2 * E * V * elem + 2 * T * 4
