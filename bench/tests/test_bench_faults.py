"""The check that decides ``correct``: a sound run passes, and a run whose
timed path is broken underneath, or the reference's lower-precision control
put in the program's place, fails.

Each test drives a whole run of a tiny cell on the CPU (the Pallas kernel
interpreted) without the harness's look for a chip.  The faults a one-chip
serving cell can have: an answer altered where it is produced, and half of
a batch left out.
"""
import pytest

from bench.control import FAULTS, plant
from bench.harness import check, passes, run_cell, run_window, set_up

PEAKS = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}
SEED = 2**31 + 4242


@pytest.fixture(scope="module")
def catalog(tiny_catalog):
    return tiny_catalog


def run(catalog, cell="tiny.closed", seed=SEED):
    import time

    return run_cell(catalog.cell(cell), catalog, seed=seed, seconds=0.5,
                    trace=False, t_start=time.perf_counter(),
                    device={"platform": "cpu", "kind": "cpu", "count": 1},
                    peaks=PEAKS, log=lambda m: None)


@pytest.mark.parametrize("cell", ["tiny.open", "tiny.closed"])
def test_sound_run_is_correct(catalog, cell):
    result = run(catalog, cell)
    assert result["correct"] is True
    assert result["checks"]["rows_checked"]["value"] > 0
    assert all(c["value"] == 0 for n, c in result["checks"].items()
               if n != "rows_checked")
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {m["name"] for m in catalog.cell(cell).end_to_end}


@pytest.mark.parametrize("fault", FAULTS)
def test_broken_timed_path_is_not_correct(catalog, fault):
    undo = plant(fault)
    try:
        result = run(catalog)
    finally:
        undo()
    assert result["correct"] is False
    assert result["checks"]["wrong_rows"]["value"] > 0


def test_control_is_not_correct(catalog):
    """The reference at 16-bit fixed point in the program's place."""
    cell = catalog.cell("tiny.closed")
    served = set_up(cell, catalog, SEED)
    window, _ = run_window(served, cell.traffic, 0.5, SEED, warmup_s=0.1)
    assert passes(check(served, window, SEED, 64))
    control = check(served, window, SEED, 64, bits=16)
    assert not passes(control)
    assert control["wrong_rows"]["value"] > 0.9 * control["rows_checked"]["value"]
