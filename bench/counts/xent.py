"""Forward cross-entropy over a (padded) vocabulary: logits, log-sum-exp
and the label's logit for each row."""
from __future__ import annotations


def flops(T: int, E: int, V: int) -> float:
    """The (T, E) × (E, V) logits product; the softmax reductions are
    O(T·V) and left out."""
    return 2.0 * T * E * V


def bytes_moved(T: int, E: int, V: int, *, elem: int = 2) -> float:
    """Read the hidden rows and the head once, read labels, write the
    per-row loss and log-sum-exp (float32)."""
    return T * E * elem + E * V * elem + T * 4 + 2 * T * 4
