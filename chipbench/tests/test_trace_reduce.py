"""The reduction from a trace to busy time, idle share, op counts and
labelled idle gaps: on a hand-made record whose answers are known, and
on a small trace recorded on the chip (chipbench/tests/data, written by
record_trace.py)."""
import json
from pathlib import Path

import pytest

from chipbench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6


def test_busy_union_gaps_and_labels():
    rec = {"spans": [("chipbench.window", 0, 100 * MS),
                     ("chipbench.dispatch", 10 * MS, 5 * MS),
                     ("chipbench.readback", 60 * MS, 30 * MS)],
           "devices": {
               "/device:TPU:0": [("fusion.1", 5 * MS, 10 * MS),
                                 ("fusion.2", 12 * MS, 8 * MS),  # overlaps
                                 ("fusion.1", 50 * MS, 20 * MS),
                                 ("late", 95 * MS, 10 * MS)],    # clipped
               "/device:TPU:1": [("fusion.1", 0, 40 * MS)]}}
    out = tr.reduce(rec)
    assert out["window_s"] == pytest.approx(0.1)
    # chip 0: [5,20] + [50,70] + [95,100] = 40 ms; chip 1: 40 ms
    assert out["busy_s"] == pytest.approx(0.040)
    assert out["ops"] == pytest.approx((4 + 1) / 2)
    names = dict(out["device_ops"])
    assert names["fusion.1"] == pytest.approx((10 + 20 + 40) / 2 / 1e3)
    assert names["late"] == pytest.approx(5 / 2 / 1e3)
    gaps = out["idle_gaps"]
    # chip 0's idle: [0,5], [20,50], [70,95]
    assert [g[1] for g in gaps] == pytest.approx([0.030, 0.025, 0.005])
    assert [g[0] for g in gaps] == ["host", "chipbench.readback",
                                    "host"]


def test_nested_ops_count_their_self_time():
    # a while loop [0, 50] whose body ran two ops, and one op after it
    rec = {"spans": [("chipbench.window", 0, 100 * MS)],
           "devices": {"/device:TPU:0": [("while", 0, 50 * MS),
                                         ("body.1", 10 * MS, 10 * MS),
                                         ("body.2", 30 * MS, 10 * MS),
                                         ("after", 60 * MS, 10 * MS)]}}
    out = tr.reduce(rec)
    assert out["busy_s"] == pytest.approx(0.060)
    assert dict(out["device_ops"]) == pytest.approx(
        {"while": 0.030, "body.1": 0.010, "body.2": 0.010, "after": 0.010})


def test_no_window_or_no_device_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce({"spans": [], "devices": {"/device:TPU:0": []}})
    with pytest.raises(ValueError):
        tr.reduce({"spans": [("chipbench.window", 0, 1)], "devices": {}})


@pytest.mark.parametrize("name", sorted(p.name for p in DATA.glob("*.json")))
def test_recorded_chip_trace(name):
    rec = json.loads((DATA / name).read_text())
    assert any(tr.DEVICE_PLANE.match(p) for p in rec["lines"])
    out = tr.reduce(rec)
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["ops"] > 0
    union = out["busy_s"] + sum(g[1] for g in out["idle_gaps"])
    if len(out["idle_gaps"]) < 10:
        # with every gap listed, busy and idle tile the window
        assert union == pytest.approx(out["window_s"], rel=1e-6)
    assert all(lbl.startswith("chipbench.") or lbl == "host"
               for lbl, _ in out["idle_gaps"])
