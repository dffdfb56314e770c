#!/usr/bin/env python3
"""Readings from which a cell's limits are set (run on the chip, by hand;
the benchmark's own runs never run this).

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,... \\
        --control-seeds 21,22,23 --out <file.json>

The program's first-step readings on every seed of ``--seeds`` (the
lower readings), then on ``--control-seeds`` the reference put in the
program's place at fp8 (the control), and the fault of half of the batch
left out planted in that reference (batch ≥ 2).  A state left unchanged
reads 1 by construction.

Every reading is compared with the float32 reference, as bench/run.py
compares it, and judged under the cell's limits as bench/run.py judges
it (``judged``): the program's readings must come out correct, the
control's and each fault's not.  One process holds the chip throughout.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def train_readings(cell, config, devs, seeds, control_seeds) -> dict:
    from bench.traffic import train_steps as ts
    ref = cell.reference
    prog = ts.build(cell, config, devs)
    prog_r = {}
    for s in seeds:
        ts.initial_state(prog, config, ref, s)
        prog_r[s] = ts.first_steps(prog, config, ref, s)
        harness.log(f"program seed {s}: losses {prog_r[s]['loss']}")
    prog.params = prog.opt_state = None
    del prog
    gc.collect()
    out = {"program": {}, "control": {}, "half_batch": {}}
    refs = {}
    for s in sorted(set(seeds) | set(control_seeds)):
        refs[s] = ts.reference_readings(cell, config, s, devs)
        if s in prog_r:
            out["program"][s] = dict(ts.compare(prog_r[s], refs[s]),
                                     worst=ts.worst_leaves(prog_r[s], refs[s]))
            harness.log(f"seed {s}: program {out['program'][s]}")
    B = int(cell.params["batch"])
    for s in control_seeds:
        c = ts.reference_readings(cell, config, s, devs, mode="fp8")
        out["control"][s] = ts.compare(c, refs[s])
        if B >= 2:
            f = ts.reference_readings(cell, config, s, devs,
                                      fault="half_batch")
            out["half_batch"][s] = ts.compare(f, refs[s])
        harness.log(f"seed {s}: control {out['control'][s]} half_batch "
                    f"{out['half_batch'].get(s)}")
    out["frozen"] = FROZEN_READS
    return out


NUMBERS = ("loss_gap", "loss0_gap", "grad_gap", "grad_median_gap",
           "change_gap", "change_median_gap")
# what a state returned unchanged reads, by construction: every leaf's
# gradient and change read 0, so a leaf reads b / max(b, median b), which
# is 1 for the largest leaf and at least 1/2 for the median of the gaps
FROZEN_READS = {"grad_gap": 1.0, "change_gap": 1.0,
                "grad_median_gap": 0.5, "change_median_gap": 0.5}


def propose_limits(out: dict) -> dict:
    """Limits from the readings, by the rule PERF.md states: the lower
    reading is the largest over the program's seeds; the upper is the
    smallest the control gives where that is 3× the lower or more, or a
    fault gives where that is 10× (3× for a state left unchanged), the
    least of these; the limit lies between them at lower^0.4 · upper^0.6,
    more room above the lower than below the upper.  A number with no
    upper reading is not compared."""
    limits, table = {}, {}
    for n in NUMBERS:
        lows = [r[n] for r in out["program"].values() if n in r]
        if not lows:
            continue
        lower = max(lows)
        ups = []
        ctrl = [r[n] for r in out.get("control", {}).values() if n in r]
        if ctrl and min(ctrl) >= 3 * lower:
            ups.append(min(ctrl))
        f = [r[n] for r in out.get("half_batch", {}).values() if n in r]
        if f and min(f) >= 10 * lower:
            ups.append(min(f))
        if "frozen" in out and FROZEN_READS.get(n, 0) >= 3 * lower:
            ups.append(FROZEN_READS[n])
        table[n] = {"lower": lower, "control_min": min(ctrl) if ctrl else None,
                    "upper": min(ups) if ups else None}
        if ups:
            upper = min(ups)
            limits[n] = float(f"{lower ** 0.4 * upper ** 0.6:.2g}")
    return {"limits": limits, "readings": table}


def judged(out: dict, limits: dict | None) -> dict:
    """``correct`` for every measured reading under ``limits``, by
    harness.judge as a run decides it, with the numbers over their limit."""
    res = {}
    for kind in ("program", "control", "half_batch"):
        res[kind] = {}
        for s, r in out.get(kind, {}).items():
            ok, checks = harness.judge(r, limits)
            res[kind][s] = {"correct": ok, "over": [
                k for k, c in checks.items()
                if c["limit"] is not None and not c["value"] <= c["limit"]]}
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", required=True)
    ap.add_argument("--write-limits", action="store_true",
                    help="write the proposed limits into the cell's file")
    args = ap.parse_args(argv)
    seeds = [int(x) for x in args.seeds.split(",")]
    control = [int(x) for x in args.control_seeds.split(",") if x]
    cell = harness.load_cell(args.workload)
    try:
        devs = harness.devices(cell.chips, rehearse=False)
    except harness.NoDevice as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    t0 = time.perf_counter()
    out = train_readings(cell, cell.config, devs, seeds, control)
    out["seconds"] = time.perf_counter() - t0
    out["proposal"] = propose_limits(out)
    harness.log(f"proposed {out['proposal']}")
    out["judged"] = judged(out, cell.params.get("limits"))
    for kind, by_seed in out["judged"].items():
        harness.log(f"{kind} under the cell's limits: " + ", ".join(
            f"{s} correct={j['correct']} over={j['over']}"
            for s, j in by_seed.items()))
    if args.write_limits:
        path = harness.BENCH / "workloads" / f"{cell.name}.json"
        params = harness.load_json(path)
        params["limits"] = out["proposal"]["limits"]
        with open(path, "w") as f:
            json.dump(params, f, indent=2)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
