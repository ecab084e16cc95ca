"""The timed path broken underneath, for each fault a cell can have: the
run completes and its ``correct`` comes out false."""
import pytest

from conftest import cells, driver_of, run_cell

FAULTS = [(w["name"], f) for w in cells()
          for f in ("unchanged", "half_batch", "answer")
          + (("no_server_update",) if driver_of(w) == "zoo_step" else ())
          + (("no_exchange",) if w["chips"] > 1 else ())]


@pytest.mark.parametrize("workload,fault", FAULTS)
def test_fault_is_not_correct(tiny, workload, fault):
    res, err = run_cell(tiny, workload, seed=2**31 + 11, seconds=0.5,
                        fault=fault)
    assert res["correct"] is False, (fault, res["compared"])
