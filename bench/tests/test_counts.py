"""The FLOP and byte counters against counts made by hand, and against
brute-force enumeration, at small shapes; each configuration's count
module found by its reference name."""
import itertools
import runpy
from pathlib import Path

import pytest

from bench import harness
from bench.counts import attention, xent
from bench.counts.models import qwen3
from bench.traffic import train_steps

BENCH = Path(__file__).resolve().parents[1]


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
    assert qwen3.train_flops(c, 1, 3) == 3600


@pytest.mark.parametrize("path", sorted((BENCH / "configs").glob("*.json")),
                         ids=lambda p: p.stem)
def test_every_config_finds_its_modules(path):
    config = harness.load_json(path)
    assert callable(harness.model_module("reference", config).loss)
    counts = harness.model_module("counts.models", config)
    small = harness.rehearsal_config(config)
    assert counts.train_flops(small, 2, 16) > 0


def test_missing_count_module_stops_the_load(monkeypatch):
    """A configuration whose reference has no count module stops when its
    cell is loaded, naming the file, before the step is built."""
    load = harness.load_json

    # bench/reference/common.py is there, bench/counts/models/common.py not
    def renamed(path):
        out = load(path)
        return dict(out, reference="common") if path.parent.name == \
            "configs" else out

    def no_step(*_a, **_kw):
        raise AssertionError("a step was built after a failed load")

    monkeypatch.setattr(harness, "load_json", renamed)
    monkeypatch.setattr(train_steps, "build", no_step)
    name = harness.cells_of_kind("train_steps")[0]
    want = "bench/counts/models/common.py"
    with pytest.raises(FileNotFoundError, match=want):
        harness.load_cell(name)
    run = runpy.run_path(str(BENCH / "run.py"), run_name="bench_run")
    with pytest.raises(FileNotFoundError, match=want):
        run["main"](["--workload", name, "--seed", "1", "--seconds", "1",
                     "--rehearse"])

