"""The reduction from the program's named scopes to per-layer device time
(bench/scopes.py): token matching, op_names read from a trace file, which
ops count, and the six readers on a synthetic step and on a step recorded
on a TPU v5e (bench/testdata/trace_v5e_train_scoped.json)."""
import importlib.util
import json
from pathlib import Path

import pytest

from bench import harness, scopes, trace as tr
from bench.counts import xent_bwd

METRICS = Path(__file__).resolve().parents[1] / "metrics"
DATA = Path(__file__).resolve().parents[1] / "testdata"
CELL = harness.load_cell("qwen3-1.7b-d7.train-4k")


@pytest.mark.parametrize("op_name,layer,ph", [
    ("jit(step_fn)/transpose(jvp(loss_head))/while/body/closed_call/"
     "dot_general", "loss_head", "bwd"),
    ("jit(step_fn)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attention/pallas_call", "attention", "remat"),
    ("jit(step_fn)/transpose(jvp())/while/body/closed_call/checkpoint/mlp/"
     "dot_general", "mlp", "bwd"),
    ("jit(step_fn)/jvp()/while/body/closed_call/attention/mul",
     "attention", "fwd"),
    ("jit(step_fn)/jvp(embed)/gather", "embed", "fwd"),
    ("jit(step_fn)/optimizer/sub", "optimizer", "fwd"),
    ("jit(step_fn)/jvp()/while/body/closed_call/moe/mlp/dot_general",
     "mlp", "fwd"),
    ("jit(step_fn)/jvp()/attention_mask/mul", None, "fwd"),
    ("jit(step_fn)/jvp()/while/body/dynamic_slice", None, "fwd"),
    ("", None, "fwd"),
])
def test_layer_and_phase(op_name, layer, ph):
    assert scopes.layer_of(op_name) == layer
    assert scopes.phase(op_name) == ph


# -- a trace file made by hand (the XSpace protobuf, field by field) --------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def _plane(name, events, stat_names=("program_id", "tf_op")):
    """An XPlane with event metadata only: ``events`` are (text, program,
    tf_op or None)."""
    ids = {s: i + 1 for i, s in enumerate(stat_names)}
    fields = [(2, name)]
    for s, i in ids.items():
        fields.append((5, _msg((1, i), (2, _msg((1, i), (2, s))))))
    for k, (text, program, op) in enumerate(events, start=1):
        stats = [(5, _msg((1, ids["program_id"]), (3, program)))]
        if op is not None:
            stats.append((5, _msg((1, ids["tf_op"]), (5, op + ":"))))
        fields.append((4, _msg((1, k), (2, _msg((1, k), (2, text),
                                                *stats)))))
    return _msg(*fields)


def test_op_names_from_trace_file():
    step, other = 12263252865049742706, 5
    xspace = _msg(
        (1, _plane("/host:CPU", [("%fusion.1 = f32[] fusion()", step,
                                  "host/ignored")])),
        (1, _plane("/device:TPU:0", [
            ("jit_step_fn(%d)" % step, step, None),
            ("jit_other(%d)" % other, other, None),
            ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
             step, "jit(step_fn)/transpose(jvp(loss_head))/while/body/dot"),
            ("%copy-start = (f32[8]{0}, u32[]) copy-start(f32[8]{0} %p)",
             step, None),
            ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
             other, "jit(other)/mlp/dot"),
        ])))
    assert scopes.op_names(xspace) == {
        "fusion.1": "jit(step_fn)/transpose(jvp(loss_head))/while/body/dot"}


# -- the reduction on a synthetic step ---------------------------------------

NAMES = {"a": "jit(step_fn)/jvp()/while/body/attention/dot",
         "r": "jit(step_fn)/transpose(jvp())/checkpoint/"
              "rematted_computation/attention/dot",
         "m": "jit(step_fn)/transpose(jvp())/checkpoint/mlp/dot",
         "x": "jit(step_fn)/transpose(jvp(loss_head))/while/body/dot",
         "o": "jit(step_fn)/optimizer/sub",
         "u": "jit(step_fn)/jvp()/while/body/dynamic_slice"}


def _step_trace():
    """Two step executions [0, 100) and [120, 220) in the window [0, 200):
    ops inside them, one op of another program in the gap, one op that
    outlasts the window."""
    ops = [("a", 0, 10), ("r", 10, 20), ("m", 20, 50), ("x", 50, 80),
           ("o", 80, 95), ("u", 95, 100),
           ("a", 105, 115),                       # another program's op
           ("a", 120, 130), ("m", 130, 170), ("x", 170, 210)]
    mods = [("jit_step_fn", 0, 100), ("jit_other", 103, 117),
            ("jit_step_fn", 120, 220)]
    dev = tr.Device("/device:TPU:0",
                    [tr.Op(n, "fusion", s, e, n) for n, s, e in ops],
                    [tr.Op(n, "module", s, e, n) for n, s, e in mods])
    return tr.Trace([dev], [], (0, 200))


def test_only_ops_inside_step_executions_count():
    st = scopes.step_times(_step_trace(), NAMES)
    assert st.steps == pytest.approx(1.8)
    assert st.ns[("attention", "fwd")] == 20          # not the other's op
    assert st.ns[("attention", "remat")] == 10
    assert st.ns[("mlp", "bwd")] == 70
    assert st.ns[("loss_head", "bwd")] == 60           # clipped at 200
    assert st.ns[("optimizer", "fwd")] == 15
    assert st.ns[(scopes.UNSCOPED, "fwd")] == 5
    assert st.per_step_ms(st.layer_ns("mlp")) == pytest.approx(70e-6 / 1.8)


def test_scopes_plus_unscoped_equal_busy():
    st = scopes.step_times(_step_trace(), NAMES)
    assert st.total_ns() == st.busy_ns == 180
    assert "unscoped 0.000" in st.line() and "busy " in st.line()


def test_unscoped_step_and_lost_scope():
    t = _step_trace()
    # a step compiled before the scopes: op_names, but no scope in them
    bare = scopes.step_times(t, {"r": "jit(step_fn)/transpose(jvp())/"
                                      "checkpoint/rematted_computation/dot"})
    assert not bare.scoped and bare.total_ns() == 180
    ctx = {"trace": t, "scope_times": bare}
    assert scopes.layer_ms(ctx, "attention") == 0.0
    assert _read("xent_bwd_roofline.train", ctx) == 0.0
    assert _read("remat_share.train", ctx) == pytest.approx(100 * 10 / 180)
    ctx = {"trace": t, "scope_times": scopes.step_times(t, {})}
    assert _read("remat_share.train", ctx) is None   # no op_name at all
    ctx = {"trace": t, "scope_times": scopes.step_times(t, NAMES)}
    assert scopes.layer_ms(ctx, "ssd") is None    # scoped, but not this one
    assert scopes.layer_ms(ctx, "attention") > 0
    assert scopes.step_times(tr.Trace([], [], (0, 1)), NAMES) is None


def _read(name, ctx):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def test_readers_on_synthetic_step():
    t = _step_trace()
    ctx = {"trace": t, "scope_times": scopes.step_times(t, NAMES),
           "batch": 1, "seq": 3,
           "config": {"vocab_size": 10, "vocab_pad_multiple": 4,
                      "hidden_size": 2},
           "peaks": {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e9}}
    per_step = 1e-6 / 1.8
    assert _read("attention_ms.train", ctx) == pytest.approx(30 * per_step)
    assert _read("mlp_ms.train", ctx) == pytest.approx(70 * per_step)
    assert _read("loss_head_ms.train", ctx) == pytest.approx(60 * per_step)
    assert _read("optimizer_ms.train", ctx) == pytest.approx(15 * per_step)
    assert _read("remat_share.train", ctx) == pytest.approx(100 * 10 / 180)
    # T 2, E 2, Vp 12: 192 flops at 1e9/s bound it (128 bytes)
    least = 192e-9
    assert _read("xent_bwd_roofline.train", ctx) == \
        pytest.approx(100 * least / (60 * per_step * 1e-3))


def test_xent_bwd_by_hand():
    assert xent_bwd.flops(5, 3, 7) == 420
    # hidden and dh 60 B, head and dW 84 B, lse and labels 40 B
    assert xent_bwd.bytes_moved(5, 3, 7) == 184


def test_recorded_scoped_trace():
    """One step of the cell traced on a TPU v5e, with the op_names the trace
    file kept for its instructions: every reader reads the recorded value,
    the scopes and ``unscoped`` add up to the busy time, and ``unscoped``
    is a small part of it."""
    with open(DATA / "trace_v5e_train_scoped.json") as f:
        rec = json.load(f)
    t = tr.Trace.from_json(rec["trace"])
    st = scopes.step_times(t, rec["op_names"])
    want = rec["expect"]
    assert st.steps == pytest.approx(want["step_runs"], rel=1e-9)
    assert st.total_ns() == pytest.approx(st.busy_ns, rel=0.01)
    assert st.layer_ns(scopes.UNSCOPED) <= 0.1 * st.busy_ns
    ctx = {"trace": t, "scope_times": st, "config": CELL.config,
           "batch": int(CELL.params["batch"]),
           "seq": int(CELL.mix["seq_len"]),
           "peaks": harness.peaks("TPU v5 lite")}
    for name in ("attention_ms.train", "mlp_ms.train", "loss_head_ms.train",
                 "optimizer_ms.train", "xent_bwd_roofline.train",
                 "remat_share.train"):
        assert _read(name, ctx) == pytest.approx(want[name], rel=1e-9), name
    assert 0 < want["xent_bwd_roofline.train"] < 100
