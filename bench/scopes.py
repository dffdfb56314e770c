"""Per-layer device time of the train step, from the program's named scopes.

The program opens one ``jax.named_scope`` at each layer boundary of the
train step (DESIGN.md §13): ``NAMES``.  A scope's name reaches every HLO
instruction's op_name through ``lax.scan``, ``jax.checkpoint`` and
``custom_vjp``; a backward op reads ``transpose(jvp(<scope>))/...`` and a
recomputed one ``.../checkpoint/rematted_computation/<scope>/...``.  On a
TPU the profiler keeps each instruction's op_name with the op's event
metadata in the trace file (the ``tf_op`` stat), beside the program it
belongs to, so the trace of the executed step names each of its ops' layer
and phase (forward, recompute, backward) itself.

Instruction names are unique within one module, not across modules, so
only ops of the step module, and only while it executes, count.  Ops whose
path names no scope are ``unscoped``.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
import time

from bench import harness, trace as tr

NAMES = ("embed", "attention", "ssd", "mlp", "moe", "loss_head", "optimizer")
PHASES = ("fwd", "remat", "bwd")
UNSCOPED = "unscoped"
STEP_MODULE = "jit_step_fn"


# ---------------------------------------------------------------------------
# op_names from the trace file (XSpace protobuf, read field by field)
# ---------------------------------------------------------------------------

def _varint(buf, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return x, i


def _fields(buf):
    """(field number, value) of each field of one protobuf message: an int
    for a varint, a memoryview of the bytes otherwise."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire} in a trace file")
        yield key >> 3, v


def _map_values(entry):
    """The value of a protobuf map entry (field 2)."""
    return next((v for f, v in _fields(entry) if f == 2), b"")


def op_names(xspace: bytes) -> dict:
    """{HLO instruction name: op_name} of the ops of the step programs
    (``STEP_MODULE*``) on the device planes of a profiler trace file (XSpace:
    planes=1; XPlane: name=2, event_metadata=4, stat_metadata=5;
    XEventMetadata: name=2, stats=5; XStat: metadata_id=1, uint64=3,
    int64=4, str=5, ref=7; XStatMetadata: name=2)."""
    out = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1:
            continue
        fields = list(_fields(plane))
        name = next((bytes(v).decode() for g, v in fields if g == 2), "")
        if not name.startswith("/device:"):
            continue
        stat_names = {}
        for g, entry in fields:
            if g == 5:
                md = dict(_fields(_map_values(entry)))
                stat_names[md.get(1, 0)] = bytes(md.get(2, b"")).decode()
        programs, ops = set(), []
        for g, entry in fields:
            if g != 4:
                continue
            text, stats = "", {}
            for h, v in _fields(_map_values(entry)):
                if h == 2:
                    text = bytes(v).decode()
                elif h == 5:
                    st = dict(_fields(v))
                    key = stat_names.get(st.get(1))
                    if 5 in st:
                        stats[key] = bytes(st[5]).decode()
                    elif 7 in st:
                        stats[key] = stat_names.get(st[7], "")
                    else:
                        stats[key] = st.get(3, st.get(4))
            m = re.fullmatch(r"(.+)\((\d+)\)", text)
            if m and m.group(1).startswith(STEP_MODULE):
                programs.add(int(m.group(2)))
            elif "tf_op" in stats:
                ops.append((text, stats.get("program_id"), stats["tf_op"]))
        for text, program, op_name in ops:
            if program in programs:
                out[tr.parse_op(text)[0]] = op_name.removesuffix(":")
    return out


def trace_file(cell_name: str) -> str:
    """The newest trace file of a cell's traced run, where bench/run.py
    writes it (``.bench_trace/<cell>``)."""
    files = sorted(glob.glob(os.path.join(harness.ROOT, ".bench_trace",
                                          cell_name, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no trace file for {cell_name}")
    return files[-1]


_SCOPE = re.compile(r"(?:^|[/(])(%s)(?=[/)]|$)" % "|".join(NAMES))


def layer_of(op_name: str) -> str | None:
    """The innermost of ``NAMES`` that occurs in the op_name path as a whole
    token (``/name/``, ``(name)``, at either end), or None."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else None


def phase(op_name: str) -> str:
    """``remat`` for an op that full remat recomputes, ``bwd`` for one of the
    backward pass, ``fwd`` otherwise."""
    if "rematted_computation" in op_name:
        return "remat"
    if "transpose(" in op_name:
        return "bwd"
    return "fwd"


@dataclasses.dataclass
class StepTimes:
    """Device time of the step executions in a traced window, by layer and
    phase, summed over the devices."""
    steps: float        # step executions in the window (the first device's)
    n_devices: int
    ns: dict            # {(layer or UNSCOPED, phase): ns}
    busy_ns: float      # union of op intervals inside the step executions
    named_ns: float     # time of the ops whose instruction has an op_name

    @property
    def scoped(self) -> bool:
        """Whether any op of the step carries a scope's name (a step compiled
        before the scopes existed carries none)."""
        return any(k[0] != UNSCOPED for k in self.ns)

    def per_step_ms(self, ns: float) -> float:
        return ns * 1e-6 / (self.steps * self.n_devices)

    def layer_ns(self, layer: str, phases=PHASES) -> float:
        return sum(self.ns.get((layer, p), 0.0) for p in phases)

    def phase_ns(self, ph: str) -> float:
        return sum(v for (_, p), v in self.ns.items() if p == ph)

    def total_ns(self) -> float:
        return sum(self.ns.values())

    def line(self) -> str:
        """One line: milliseconds per step of each scope × phase (fwd, remat,
        bwd), then unscoped, their sum and the step's busy time."""
        parts = []
        for layer in NAMES + (UNSCOPED,):
            ms = [self.per_step_ms(self.ns.get((layer, p), 0.0))
                  for p in PHASES]
            if any(ms):
                parts.append(f"{layer} " + "/".join(f"{x:.3f}" for x in ms))
        return (f"scope ms/step (fwd/remat/bwd) over {self.steps:.3f} steps: "
                + ", ".join(parts)
                + f"; sum {self.per_step_ms(self.total_ns()):.3f}"
                + f", busy {self.per_step_ms(self.busy_ns):.3f}")


def step_times(trace: tr.Trace, names: dict) -> StepTimes | None:
    """The reduction: each op inside a step execution in the window, by the
    layer and phase of its instruction's op_name (``names``, from
    :func:`op_names`).  None where the window holds no step."""
    lo, hi = trace.window
    if not trace.devices:
        return None
    steps = tr.module_runs(trace.devices[0], STEP_MODULE, lo, hi)
    if steps == 0:
        return None
    keys = {}
    ns = collections.defaultdict(float)
    busy = named = 0.0
    for dev in trace.devices:
        spans = tr.merge([(m.start, m.end) for m in dev.modules
                          if m.name.startswith(STEP_MODULE)], lo, hi)
        starts = [s for s, _ in spans]
        inside = []
        for o in dev.ops:
            i = max(bisect.bisect_right(starts, o.start) - 1, 0)
            while i < len(spans) and spans[i][0] < o.end:
                s, e = max(o.start, spans[i][0]), min(o.end, spans[i][1])
                if e > s:
                    if o.name not in keys:
                        name = names.get(o.name, "")
                        keys[o.name] = (layer_of(name) or UNSCOPED,
                                        phase(name))
                    ns[keys[o.name]] += e - s
                    named += (e - s) * (o.name in names)
                    inside.append((s, e))
                i += 1
        busy += tr.covered(inside, lo, hi)
    return StepTimes(steps, len(trace.devices), dict(ns), busy, named)


def read_step(ctx: dict) -> StepTimes | None:
    """The step's times for a metric reader: reduced once per run, kept in
    the reader context for the readers that follow, and logged as one
    stderr line with the host seconds the reduction took."""
    if "scope_times" not in ctx:
        st = None
        if ctx["trace"].devices:
            t0 = time.perf_counter()
            with open(trace_file(ctx["cell"].name), "rb") as f:
                names = op_names(f.read())
            st = step_times(ctx["trace"], names)
            if st is not None:
                harness.log(f"{st.line()} (read in "
                            f"{time.perf_counter() - t0:.2f}s)")
        ctx["scope_times"] = st
    return ctx["scope_times"]


def layer_ms(ctx: dict, layer: str, phases=PHASES) -> float | None:
    """Device ms per step of the ops under ``layer`` in ``phases``.  None
    where the window holds no step, or where the step carries the scopes but
    none of its ops lies under ``layer`` (a renamed or lost scope: on the
    chip that fails the run).  0 where the step carries no scope at all."""
    st = read_step(ctx)
    if st is None:
        return None
    if not st.scoped:
        return 0.0
    ns = st.layer_ns(layer, phases)
    return st.per_step_ms(ns) if ns > 0 else None
