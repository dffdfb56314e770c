"""What every cell shares: the spec files, the device check, the compile
cache, the profiler window, the per-layer metric readers and the result
line.  Everything a cell, a traffic mix, a configuration or a metric owns
lives in files of its own, found here by the name in BENCHMARK.json:

    bench/configs/<config>.json      sizes, source, reference name
    bench/reference/<reference>.py   its plain float32 reference
    bench/counts/models/<reference>.py  its model FLOPs per train step,
                                     train_flops(config, batch, seq)
    bench/mixes/<traffic>.json       traffic parameters; "kind" names the
                                     generator bench/traffic/<kind>.py
    bench/workloads/<cell>.json      batch, kernels, limits of one cell
    bench/metrics/<metric>.py        read(ctx) → number, or None where
                                     there is nothing to read
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import os
import sys
import time
import types
from pathlib import Path

import jax
from jax import monitoring

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoDevice(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell needs."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One workload of BENCHMARK.json with the files it names."""
    spec: dict                       # the BENCHMARK.json entry
    params: dict                     # bench/workloads/<name>.json
    config: dict                     # bench/configs/<config>.json
    mix: dict                        # bench/mixes/<traffic>.json
    end_to_end: list                 # metric entries this cell reports
    per_layer: list
    reference: types.ModuleType      # bench/reference/<reference>.py
    counts: types.ModuleType         # bench/counts/models/<reference>.py

    @property
    def name(self) -> str:
        return self.spec["name"]

    @property
    def chips(self) -> int:
        return int(self.spec["chips"])


def model_module(package: str, config: dict) -> types.ModuleType:
    """bench/<package>/<reference>.py for the ``reference`` name in the
    configuration's file.  A file that is not there stops the run at once,
    naming it, before anything is built."""
    name = config["reference"]
    rel = Path("bench", *package.split("."), f"{name}.py")
    if not (ROOT / rel).is_file():
        raise FileNotFoundError(
            f"configuration {config['name']!r} names the reference "
            f"{name!r}, but {rel} is not there")
    return importlib.import_module(f"bench.{package}.{name}")


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> Cell:
    bench = load_json(ROOT / "BENCHMARK.json")
    specs = {w["name"]: w for w in bench["workloads"]}
    if name not in specs:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(specs)}")
    return make_cell(
        specs[name], load_json(BENCH / "workloads" / f"{name}.json"),
        [m for m in bench["end_to_end"] if _reports(m, name)],
        [m for m in bench["per_layer"] if _reports(m, name)])


def make_cell(spec: dict, params: dict, end_to_end=(), per_layer=()) -> Cell:
    """A cell from its BENCHMARK.json-style entry and its parameters, with
    its configuration's reference and count modules found."""
    config = load_json(BENCH / "configs" / f"{spec['config']}.json")
    return Cell(
        spec=spec, params=params, config=config,
        mix=load_json(BENCH / "mixes" / f"{spec['traffic']}.json"),
        end_to_end=list(end_to_end), per_layer=list(per_layer),
        reference=model_module("reference", config),
        counts=model_module("counts.models", config))


def cells_of_kind(kind: str) -> list:
    """The names of BENCHMARK.json's cells whose traffic mix has ``kind``."""
    bench = load_json(ROOT / "BENCHMARK.json")
    return [w["name"] for w in bench["workloads"]
            if load_json(BENCH / "mixes" / f"{w['traffic']}.json")["kind"]
            == kind]


def rehearsal_config(config: dict) -> dict:
    """The configuration shrunk to its ``rehearsal`` sizes (CPU runs)."""
    small = dict(config)
    over = dict(config["rehearsal"])
    program = dict(config["program"], **over.pop("program"))
    small.update(over)
    small["program"] = program
    return small


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def devices(chips: int, *, rehearse: bool):
    """The ``chips`` devices the cell runs on.  Off a TPU only a
    rehearsal may go on, and it never reports device metrics."""
    devs = jax.devices()
    if devs[0].platform != "tpu" and not rehearse:
        raise NoDevice(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"needs {chips} chip(s); JAX found {len(devs)}")
    return devs[:chips]


def device_info(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def peaks(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[kind]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: $JAX_COMPILATION_CACHE_DIR where
    set (JAX reads it itself), else <checkout>/.jax_cache.  Every program
    is cached, however quickly it compiled, so that a second run compiles
    nothing."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(ROOT / ".jax_cache")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts backend compilations and persistent-cache loads while
    ``armed`` (the measured window should see none of either)."""

    def __init__(self):
        self.armed = False
        self.compiles = 0
        self.cache_loads = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if not self.armed:
            return
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
        elif name == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.cache_loads += 1


# ---------------------------------------------------------------------------
# the traced window
# ---------------------------------------------------------------------------

class TraceWindow:
    """The profiler on for the first ``seconds`` of the measured window.

    ``span(name)`` marks what the host is doing (data fetch, dispatch,
    readback, ...) so the reduction can name the device's idle gaps."""

    def __init__(self, out_dir: Path | None, seconds: float):
        self.dir = out_dir
        self.seconds = seconds
        self.on = False
        self.t0 = self.t1 = None

    def start(self):
        if self.dir is None:
            return
        jax.profiler.start_trace(str(self.dir))
        self.on = True
        self.t0 = time.perf_counter()
        self._ann = jax.profiler.TraceAnnotation("bench.window")
        self._ann.__enter__()

    def due(self) -> bool:
        return self.on and time.perf_counter() - self.t0 >= self.seconds

    def stop(self):
        if not self.on:
            return
        self._ann.__exit__(None, None, None)
        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()
        self.on = False

    def span(self, name: str):
        if not self.on:
            return _NULL
        return jax.profiler.TraceAnnotation(f"bench.{name}")


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()


# ---------------------------------------------------------------------------
# per-layer metrics
# ---------------------------------------------------------------------------

def read_metrics(entries: list, ctx: dict, *, required: bool) -> dict:
    """Run bench/metrics/<name>.py for each entry.  A reader that finds
    nothing to read returns None.  Where ``required`` (a run on the chip),
    that is an error: the cell lists the metric, so its reader has lost
    what it reads (a renamed kernel, module or source file)."""
    out = {}
    for m in entries:
        path = BENCH / "metrics" / f"{m['name']}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{m['name'].replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(ctx)
        if value is None:
            if required:
                raise ValueError(f"metric {m['name']} found nothing to read "
                                 f"in {ctx['cell'].name}")
            continue
        if not math.isfinite(value):
            raise ValueError(f"metric {m['name']} read {value}")
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# the result
# ---------------------------------------------------------------------------

def check_lines(checks: dict) -> list:
    return [f"check {k}: {v['value']!r} (limit {v['limit']!r})"
            for k, v in checks.items()]


def emit(result: dict) -> None:
    """Print each compared number beside its limit as the last lines of
    stderr, then the result as the last line of stdout."""
    checks = result.get("checks", {})
    for line in check_lines(checks):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    ordered = {k: result[k] for k in ("correct", "attempted", "failed",
                                      "metrics", "device")}
    if "breakdown" in result:
        ordered["breakdown"] = result["breakdown"]
    ordered["checks"] = checks
    print(json.dumps(ordered), flush=True)


def judge(values: dict, limits: dict | None) -> tuple[bool | None, dict]:
    """Each compared number beside its limit; correct when every number
    is finite and within its limit (None when the cell has no limits)."""
    if not limits:
        return None, {k: {"value": v, "limit": None}
                      for k, v in values.items()}
    checks = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)

