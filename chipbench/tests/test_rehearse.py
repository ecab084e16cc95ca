"""Every cell of BENCHMARK.json and the deferred four-chip cell, run
through the harness on the host CPU at a tiny size (four-chip cells on
four host devices): plain and traced, each with a result line that names
its metrics and ends in the numbers compared."""
import json

import pytest

from conftest import cells, run_cell

CELLS = [w["name"] for w in cells()]


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_checks(tiny, workload, trace):
    bench = json.loads((tiny / "BENCHMARK.json").read_text())
    res, err = run_cell(tiny, workload, seed=2**31 + 7, trace=trace)
    assert res["correct"] is True, err[-3000:]
    assert res["attempted"] > 0 and res["failed"] == 0
    want = bench["per_layer" if trace else "end_to_end"]
    names = {m["name"] for m in want
             if workload in m.get("workloads", [workload])}
    # the host CPU keeps no memory statistics to read a peak from
    assert set(res["metrics"]) == names - {"peak_hbm_gb"}
    assert list(res)[-1] == "compared"
    assert res["compared"]["window_compiles"] == {"value": 0, "limit": 0}
    chips = {w["name"]: w["chips"] for w in bench["workloads"]}[workload]
    assert res["device"]["count"] == chips
    if trace:
        assert res["device"]["busy_s"] > 0
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
