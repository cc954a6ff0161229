"""The correctness check refuses the control and every planted fault, and
passes the program as it is: whole runs of each cell of ``BENCHMARK.json``,
and of the toy star's join cell, at its configuration's ``small`` size on
the CPU, past the harness's look for a chip.  (On the chip the control is
run at the cells' own size by ``bench/control.py``.)"""

import contextlib

import pytest

import datagen
import faults
import records
import run

CASES = ["sound", "control"] + sorted(faults.FAULTS)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("cell, root", records.cells())
def test_check_refuses_the_control_and_every_fault(cell, root, case):
    bench = run.load_benchmark()
    patch = {
        "sound": contextlib.nullcontext,
        "control": faults.control,
        **faults.FAULTS,
    }[case]
    small = datagen.load_config(cell["config"], root)["small"]
    with patch():
        result = run.run_cell(cell, bench, seed=31, seconds=3.0, trace=False,
                              overrides=small, root=root)
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["correct"] is (case == "sound"), result["compared"]
