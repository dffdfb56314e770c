#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in BENCHMARK.json, makes its weights and inputs from
the seed, warms up, measures for ``--seconds`` and checks the timed path's
output against the plain reference.  The last line of stdout is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks`` (each compared number beside its limit, also the last lines of
stderr).  Without a TPU, or with fewer chips than the cell needs, it
exits 2 and prints no result.

``--rehearse`` runs the cell end to end at its configuration's tiny
``rehearsal`` sizes on whatever JAX finds (the CPU here), prints a
``{"rehearsal": ...}`` line instead of a result, reports no device metric,
and exits 3.  ``BENCH_KEEP_TRACE=<file>`` saves a traced run's reduced
trace as JSON (how ``bench/testdata`` was recorded).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, trace as tr  # noqa: E402


def main(argv=None, *, fault: str | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be non-negative")

    cell = harness.load_cell(args.workload)
    config = cell.config
    if args.rehearse:
        config = harness.rehearsal_config(config)
        cell.mix = dict(cell.mix, **cell.mix.get("rehearsal", {}))
        cell.params = dict(cell.params, **cell.params.get("rehearsal", {}))
    try:
        devs = harness.devices(cell.chips, rehearse=args.rehearse)
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    counter = harness.CompileCounter()
    trace_dir = None
    if args.trace:
        trace_dir = ROOT / ".bench_trace" / cell.name
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    gen = importlib.import_module(f"bench.traffic.{cell.mix['kind']}")
    ctx = {"cell": cell, "config": config, "seed": args.seed,
           "seconds": args.seconds, "devices": devs, "t_start": T_START,
           "trace_dir": trace_dir, "compiles": counter, "fault": fault,
           "trace_seconds": min(args.seconds,
                                float(cell.mix.get("trace_seconds", 3)))}
    res = gen.run(ctx)

    device, breakdown = res["device"], None
    if args.trace:
        t = tr.load(str(trace_dir))
        lo, hi = t.window
        if args.rehearse:           # exercise the reduction, report nothing
            peaks = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
        else:
            peaks = harness.peaks(device["kind"])
        lctx = dict(res.get("layer_ctx", {}), trace=t, config=config,
                    cell=cell, chips=cell.chips, peaks=peaks,
                    kernels=tr.kernel_map(res["hlo"]))
        metrics = harness.read_metrics(cell.per_layer, lctx,
                                       required=not args.rehearse)
        if t.devices:
            breakdown = {"device_ops": tr.top_ops(t.devices[0], lo, hi),
                         "idle_gaps": tr.idle_gaps(t)}
            device["busy_s"] = sum(tr.busy_ns(d, lo, hi)
                                   for d in t.devices) / len(t.devices) * 1e-9
            device["window_s"] = (hi - lo) * 1e-9
        elif not args.rehearse:
            raise RuntimeError("the trace holds no device plane")
        if os.environ.get("BENCH_KEEP_TRACE"):
            tr.save(t, os.environ["BENCH_KEEP_TRACE"])
    else:
        metrics = {m["name"]: {"value": float(res["end_to_end"][m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    if args.rehearse:
        print(json.dumps({"rehearsal": True, "correct": res["correct"],
                          "attempted": res["attempted"],
                          "failed": res["failed"],
                          "read": sorted(metrics), "checks": res["checks"]}),
              flush=True)
        return 3
    result = {"correct": bool(res["correct"]), "attempted": res["attempted"],
              "failed": res["failed"], "metrics": metrics, "device": device,
              "checks": res["checks"]}
    if breakdown is not None:
        result["breakdown"] = breakdown
    harness.emit(result)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
