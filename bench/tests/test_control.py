"""The control: the plain reference put in the program's place at fp8
(bench/reference/common.py::fp8_round) must fail the cell's comparison,
where the program passes it, in every training cell of BENCHMARK.json.
On the CPU at the cells' rehearsal sizes, with their rehearsal limits; on
the chip at the cells' own sizes the same readings come from
bench/calibrate.py, kept in bench/testdata/calibrate_v5e_<cell>.json and
judged here under the cell's own limits (PERF.md gives them)."""
import jax
import pytest

from bench import calibrate, harness
from bench.traffic import train_steps as ts

# bench/calibrate.py's chip readings at a cell's own size, one file a cell
CHIP_READINGS = sorted(
    (harness.BENCH / "testdata").glob("calibrate_v5e_*.json"))


def small(name):
    cell = harness.load_cell(name)
    config = harness.rehearsal_config(cell.config)
    cell.mix = dict(cell.mix, **cell.mix["rehearsal"])
    cell.params = dict(cell.params, **cell.params["rehearsal"])
    return cell, config


@pytest.mark.parametrize("name", harness.cells_of_kind("train_steps"))
def test_train_control_fails_where_program_passes(name):
    cell, config = small(name)
    devs = jax.devices()[:1]
    ref = cell.reference
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


@pytest.mark.parametrize("path", CHIP_READINGS,
                         ids=lambda p: p.stem.removeprefix("calibrate_v5e_"))
def test_chip_control_fails_the_cells_limits(path):
    """The readings bench/calibrate.py took on the chip at the cell's own
    size (PERF.md §4), judged under the cell's own limits as a run judges
    them: every program seed correct, every control and fault seed not."""
    cell = harness.load_cell(path.stem.removeprefix("calibrate_v5e_"))
    got = calibrate.judged(harness.load_json(path), cell.params["limits"])
    assert all(j["correct"] for j in got["program"].values()), got["program"]
    for kind in ("control", "half_batch"):
        assert got[kind], kind
        assert not any(j["correct"] for j in got[kind].values()), got[kind]
