"""The FLOP and byte counters against counts made by hand, and against
brute-force enumeration, at small shapes."""
import itertools

import pytest

from bench.counts import attention, model, xent


def test_causal_pairs_enumerated():
    for S in (1, 2, 5, 8):
        assert attention.causal_pairs(S) == sum(
            1 for q, k in itertools.product(range(S), repeat=2) if k <= q)


def test_attention_by_hand():
    # 10 kept pairs × 2 heads × (QK: 2·3 + PV: 2·3 flops) = 240
    assert attention.flops(1, 4, 2, 3, backward=False) == 240
    assert attention.flops(1, 4, 2, 3, backward=True) == 720
    # q 48 B, k and v 24 B each, o 48 B, row statistics 32 B
    assert attention.bytes_moved(1, 4, 2, 1, 3, backward=False) == 176


def test_xent_by_hand():
    assert xent.flops(5, 3, 7) == 210
    # hidden 30 B + head 42 B + labels 20 B + loss and lse 40 B
    assert xent.bytes_moved(5, 3, 7) == 132


def test_qwen3_train_flops_by_hand():
    c = {"hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 1,
         "head_dim": 2, "intermediate_size": 8, "num_hidden_layers": 1,
         "vocab_size": 10}
    # matmul params: q,o 2·4·4 + k,v 2·4·2 + mlp 3·4·8 + head 40 = 184
    # 6·184·3 tokens = 3312; attention 3·(4·2·2·6) = 288
    assert model.qwen3_train_flops(c, 1, 3) == 3600

