"""Reduce a profiler trace (``.xplane.pb``) to the intervals the per-layer
metrics read.

On a TPU each ``/device:TPU:<n>`` plane carries the lines ``XLA Modules``
(one event per program execution), ``XLA Ops`` (one event per HLO
instruction run, a loop's events enclosing its body's).  An op event's
name is the HLO instruction's text, ``%<name> = <type> <opcode>(...)``.
The host plane carries the benchmark's own ``bench.<what>`` spans on the
same clock.  Times here are nanoseconds from the start of the trace.
"""
from __future__ import annotations

import base64
import dataclasses
import glob
import json
import os
import re

# events that enclose other ops: their time is their children's
CONTAINERS = {"while", "conditional", "call"}


@dataclasses.dataclass
class Op:
    name: str            # HLO instruction name, or a module's name
    opcode: str
    start: float
    end: float
    label: str = ""      # instruction name with its result type


@dataclasses.dataclass
class Device:
    name: str
    ops: list            # leaf ops of the "XLA Ops" line
    modules: list        # "XLA Modules" line


@dataclasses.dataclass
class Trace:
    devices: list
    spans: list          # (name, start, end) of bench.* host spans
    window: tuple        # (start, end) of the bench.window span

    def to_json(self) -> dict:
        return {"devices": [{"name": d.name,
                             "ops": [dataclasses.astuple(o) for o in d.ops],
                             "modules": [dataclasses.astuple(o)
                                         for o in d.modules]}
                            for d in self.devices],
                "spans": self.spans, "window": list(self.window)}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(devices=[Device(x["name"], [Op(*o) for o in x["ops"]],
                                   [Op(*o) for o in x["modules"]])
                            for x in d["devices"]],
                   spans=[tuple(s) for s in d["spans"]],
                   window=tuple(d["window"]))


_INST = re.compile(r"^(?:ROOT\s+)?%?([^\s=]+)\s*=\s*")


def parse_op(text: str) -> tuple[str, str, str]:
    """HLO instruction text → (name, opcode, 'name type')."""
    m = _INST.match(text)
    if not m:
        return text, text, text
    name = m.group(1)
    rest = text[m.end():]
    if rest.startswith("("):                  # tuple type: skip to its close
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        typ, rest = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        typ, _, rest = rest.partition(" ")
    opcode = rest.split("(", 1)[0].strip()
    return name, opcode, f"{name} {typ[:60]}"


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    devices, spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") or \
                plane.name.startswith("/device:CPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            dev = Device(plane.name, [], [])
            for ev in _events(lines.get("XLA Ops")):
                name, opcode, label = parse_op(ev.name)
                if opcode not in CONTAINERS:
                    dev.ops.append(Op(name, opcode, ev.start_ns,
                                      ev.start_ns + ev.duration_ns, label))
            for ev in _events(lines.get("XLA Modules")):
                mod = ev.name.split("(", 1)[0]
                dev.modules.append(Op(mod, "module", ev.start_ns,
                                      ev.start_ns + ev.duration_ns, mod))
            if dev.ops or dev.modules:
                devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append((ev.name[6:], ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    window = [s for s in spans if s[0] == "window"]
    if not window:
        raise ValueError("the trace holds no bench.window span")
    devices.sort(key=lambda d: d.name)
    return Trace(devices, [s for s in spans if s[0] != "window"],
                 (window[0][1], window[0][2]))


def _events(line):
    return [] if line is None else line.events


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def merge(intervals, lo: float, hi: float) -> list:
    """Sorted, disjoint union of ``intervals`` clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def covered(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in merge(intervals, lo, hi))


# ---------------------------------------------------------------------------
# reductions the metric readers share
# ---------------------------------------------------------------------------

def busy_ns(dev: Device, lo: float, hi: float) -> float:
    """Time in [lo, hi] in which some operation ran on ``dev``."""
    return covered([(o.start, o.end) for o in dev.ops], lo, hi)


def idle_share(trace: Trace) -> float:
    """1 - busy / window, averaged over the devices."""
    lo, hi = trace.window
    shares = [1.0 - busy_ns(d, lo, hi) / (hi - lo) for d in trace.devices]
    return sum(shares) / len(shares)


def module_runs(dev: Device, prefix: str, lo: float, hi: float) -> float:
    """Executions of the modules named ``prefix*`` inside [lo, hi], each
    counted by the share of its duration that lies inside."""
    n = 0.0
    for m in dev.modules:
        if m.name.startswith(prefix) and m.end > m.start:
            inside = max(0.0, min(m.end, hi) - max(m.start, lo))
            n += inside / (m.end - m.start)
    return n


def ops_ns(trace: Trace, names) -> float:
    """Summed device time (all devices) inside the window of the ops whose
    HLO instruction is in ``names``."""
    lo, hi = trace.window
    total = 0.0
    for d in trace.devices:
        total += sum(max(0.0, min(o.end, hi) - max(o.start, lo))
                     for o in d.ops if o.name in names)
    return total


def kernel_ns(trace: Trace, kernels: dict, patterns: tuple) -> float:
    """Summed device time (all devices) of the custom calls whose kernel
    body names one of ``patterns``.  ``kernels``: {instruction name:
    kernel body bytes} of the programs that ran (see :func:`kernel_map`)."""
    return ops_ns(trace, {inst for inst, body in kernels.items()
                          if any(p.encode() in body for p in patterns)})


def top_ops(dev: Device, lo: float, hi: float, n: int = 10) -> list:
    """[(instruction label, seconds)] of the ops that took most time."""
    agg: dict = {}
    for o in dev.ops:
        t = max(0.0, min(o.end, hi) - max(o.start, lo))
        if t > 0:
            agg[o.label] = agg.get(o.label, 0.0) + t
    top = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in top]


def idle_gaps(trace: Trace, n: int = 10, min_ns: float = 1e4) -> list:
    """[(what the host was doing, seconds)] for the ``n`` longest gaps in
    which the first device ran nothing, named by the host span that
    overlaps the gap most ("none" where no span does)."""
    lo, hi = trace.window
    dev = trace.devices[0]
    busy = merge([(o.start, o.end) for o in dev.ops], lo, hi)
    gaps, cur = [], lo
    for s, e in busy:
        if s - cur >= min_ns:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi - cur >= min_ns:
        gaps.append((cur, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        best, what = 0.0, "none"
        for name, a, b in trace.spans:
            ov = min(b, e) - max(a, s)
            if ov > best:
                best, what = ov, name
        out.append([what, (e - s) * 1e-9])
    return out


# ---------------------------------------------------------------------------
# kernels by name, from the compiled programs
# ---------------------------------------------------------------------------

_BODY = re.compile(r'\\?"body\\?"\s*:\s*\\?"([A-Za-z0-9+/=]+)')


def kernel_map(hlo_texts) -> dict:
    """{HLO instruction name: kernel body bytes} for every Mosaic custom
    call in the compiled programs' texts.  A Pallas kernel's function
    name lives in its serialized body, so a metric finds its kernel by
    the name it was written under, whatever the instruction is called."""
    out = {}
    for text in hlo_texts:
        for line in text.splitlines():
            if 'custom_call_target="tpu_custom_call"' not in line:
                continue
            name = parse_op(line.strip())[0]
            m = _BODY.search(line)
            if m:
                out[name] = base64.b64decode(m.group(1) + "=" *
                                             (-len(m.group(1)) % 4))
    return out


def save(trace: Trace, path: str) -> None:
    with open(path, "w") as f:
        json.dump(trace.to_json(), f)
