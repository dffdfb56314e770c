"""The reduction from trace to metrics: interval arithmetic, op parsing,
kernels by name, and every reduction on a small trace recorded on a
TPU v5e (bench/testdata/trace_v5e_train.json)."""
import base64
import json
from pathlib import Path

import pytest

from bench import trace as tr

DATA = Path(__file__).resolve().parents[1] / "testdata"


def test_parse_op():
    name, op, label = tr.parse_op(
        "%while.12 = (s32[]{:T(128)}, bf16[1,4096,2048]{1,2,0}) "
        "while((s32[], bf16[1,4096,2048]) %tuple.3), condition=%c")
    assert (name, op) == ("while.12", "while")
    name, op, _ = tr.parse_op(
        "%fusion.1 = f32[152064,2048]{1,0:T(8,128)} fusion(f32[1] %x)")
    assert (name, op) == ("fusion.1", "fusion")
    name, op, _ = tr.parse_op(
        "%all-reduce-start.3 = f32[8]{0} all-reduce-start(f32[8]{0} %p)")
    assert (name, op) == ("all-reduce-start.3", "all-reduce-start")
    name, op, _ = tr.parse_op(
        "ROOT %add.7 = f32[8]{0} add(f32[8]{0} %a, f32[8]{0} %b)")
    assert (name, op) == ("add.7", "add")


def test_intervals():
    assert tr.merge([(0, 2), (1, 3), (5, 6), (7, 9)], 0, 8) == \
        [[0, 3], [5, 6], [7, 8]]
    assert tr.covered([(0, 2), (1, 3), (5, 6)], 1, 10) == 3


def _trace(ops, spans=(), window=(0, 100), modules=()):
    dev = tr.Device("/device:TPU:0",
                    [tr.Op(n, o, s, e, n) for n, o, s, e in ops],
                    [tr.Op(n, "module", s, e, n) for n, s, e in modules])
    return tr.Trace([dev], list(spans), window)


def test_idle_share():
    t = _trace([("a", "fusion", 0, 40), ("b", "fusion", 30, 70),
                ("c", "fusion", 80, 90)])
    assert tr.busy_ns(t.devices[0], 0, 100) == 80
    assert tr.idle_share(t) == pytest.approx(0.2)


def test_idle_gaps_named_by_host_span():
    t = _trace([("a", "fusion", 0, 10), ("b", "fusion", 50, 60)],
               spans=[("data_fetch", 5, 45), ("loss_readback", 61, 99)],
               window=(0, 100))
    gaps = tr.idle_gaps(t, min_ns=1)
    assert gaps[0][0] == "data_fetch" and gaps[0][1] == pytest.approx(40e-9)
    assert gaps[1][0] == "loss_readback"


def test_module_runs_count_partial_overlap():
    t = _trace([], modules=[("jit_step_fn", 0, 40), ("jit_step_fn", 40, 80),
                            ("jit_other", 80, 90), ("jit_step_fn", 90, 130)])
    assert tr.module_runs(t.devices[0], "jit_step_fn", 10, 100) == \
        pytest.approx(0.75 + 1 + 0.25)


def test_kernel_by_name():
    body = base64.b64encode(b"\x00func _flash_fwd_kernel\x00").decode()
    hlo = ('  %jvp__.1 = (bf16[1]{0}) custom-call(%a), '
           'custom_call_target="tpu_custom_call", backend_config={'
           '"custom_call_config":{"body":"' + body + '"}}')
    kernels = tr.kernel_map([hlo])
    assert set(kernels) == {"jvp__.1"}
    t = _trace([("jvp__.1", "custom-call", 10, 30),
                ("fusion.2", "fusion", 30, 50)])
    assert tr.kernel_ns(t, kernels, ("_flash_fwd_kernel",)) == 20
    assert tr.kernel_ns(t, kernels, ("_xent_kernel",)) == 0


def test_recorded_trace():
    with open(DATA / "trace_v5e_train.json") as f:
        rec = json.load(f)
    t = tr.Trace.from_json(rec["trace"])
    lo, hi = t.window
    want = rec["expect"]
    assert tr.idle_share(t) == pytest.approx(want["idle_share"], rel=1e-9)
    assert tr.module_runs(t.devices[0], "jit_step_fn", lo, hi) == \
        pytest.approx(want["step_runs"], rel=1e-9)
    assert tr.kernel_ns(t, {k: v.encode() for k, v in
                            rec["kernels"].items()},
                        ("_flash_fwd_kernel",)) == \
        pytest.approx(want["flash_fwd_ns"], rel=1e-9)
    top = tr.top_ops(t.devices[0], lo, hi)
    assert [round(s, 12) for _, s in top] == \
        sorted([round(s, 12) for _, s in top], reverse=True)
    assert top[0][0] == want["top_op"]
    assert 0 <= tr.idle_share(t) < 1
