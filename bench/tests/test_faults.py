"""A broken timed path reads ``correct: false``.

Each test drives a whole run of a cell at its rehearsal sizes on the CPU
(``bench/run.py --rehearse``: the look for a chip is skipped, everything
else runs), with one fault planted underneath the timed path, and reads
the run's verdict from its last line."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]

# every training cell of BENCHMARK.json, each with every fault of the step
TRAIN_FAULTS = ("frozen", "half_batch")
CASES = [(cell, fault) for cell in harness.cells_of_kind("train_steps")
         for fault in TRAIN_FAULTS]


def rehearse(cell: str, fault: str | None, seed: int = 7) -> dict:
    code = ("import sys; sys.argv[0] = 'bench/run.py'; "
            "sys.path[:0] = ['.', 'src']; import runpy; "
            "m = runpy.run_path('bench/run.py', run_name='bench_run'); "
            f"sys.exit(m['main']({['--workload', cell, '--seed', str(seed), '--seconds', '2', '--trace', '0', '--rehearse']!r}, fault={fault!r}))")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 3, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", sorted({c for c, _ in CASES}))
def test_sound_run_reads_correct(cell):
    res = rehearse(cell, None)
    assert res["correct"] is True, res["checks"]


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_reads_incorrect(cell, fault):
    res = rehearse(cell, fault)
    assert res["rehearsal"] is True
    assert res["correct"] is False, res["checks"]

