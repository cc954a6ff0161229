"""The correctness check refuses the control and every planted fault, and
passes the program as it is: whole runs of each cell at a small size on the
CPU, past the harness's look for a chip.  (On the chip the control is run
at the cells' own size by ``bench/control.py``.)"""

import contextlib

import pytest

import faults
import run

SMALL = {
    "ssb-lo.q1-ranges": {"rows": 4096, "orders": 1024},
    "tax.state-salary": {"rows": 4096, "zips": 256, "domains": {"state": 51, "zip": 256}},
}
CASES = ["sound", "control"] + sorted(faults.FAULTS)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("cell_name", sorted(SMALL))
def test_check_refuses_the_control_and_every_fault(cell_name, case):
    bench = run.load_benchmark()
    cell = run.find_cell(bench, cell_name)
    patch = {
        "sound": contextlib.nullcontext,
        "control": faults.control,
        **faults.FAULTS,
    }[case]
    with patch():
        result = run.run_cell(cell, bench, seed=31, seconds=3.0, trace=False,
                              overrides=SMALL[cell_name])
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["correct"] is (case == "sound"), result["compared"]
