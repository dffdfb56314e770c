"""The control: the plain reference put in the program's place at fp8
(bench/reference/common.py::fp8_round) must fail the cell's comparison,
where the program passes it.  On the CPU at the cells' rehearsal sizes,
with their rehearsal limits; on the chip at the cells' own sizes the same
readings come from bench/calibrate.py (PERF.md gives them)."""
import jax
import pytest

from bench import harness
from bench.traffic import train_steps as ts


def small(name):
    cell = harness.load_cell(name)
    config = harness.rehearsal_config(cell.config)
    cell.mix = dict(cell.mix, **cell.mix["rehearsal"])
    cell.params = dict(cell.params, **cell.params["rehearsal"])
    return cell, config


@pytest.mark.parametrize("name", ["qwen3-1.7b-d7.train-4k"])
def test_train_control_fails_where_program_passes(name):
    cell, config = small(name)
    devs = jax.devices()[:1]
    ref = cell.reference()
    limits = cell.params["limits"]
    prog = ts.build(cell, config, devs)
    seed = 1
    ts.initial_state(prog, config, ref, seed)
    sound = ts.compare(ts.first_steps(prog, config, ref, seed),
                       truth := ts.reference_readings(cell, config, seed,
                                                      devs))
    control = ts.compare(ts.reference_readings(cell, config, seed, devs,
                                               mode="fp8"), truth)
    assert all(sound[k] <= limits[k] for k in limits), sound
    assert any(control[k] > limits[k] for k in limits), control

