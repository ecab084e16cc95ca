"""Live health plane (docs/observability.md): online detectors score
streaming trace records into structured alerts, the monitor collector
recovers a crashed process's records from its side-socket ring, and the
bench regression gate enforces the committed perf trajectory.

Detector units here feed hand-built records — each test pins one firing
condition AND the matching silence guard (warmup, floor, once-per-
episode), because the acceptance for the whole plane is double-sided:
injected faults must alert within a bounded number of rounds while a
clean run on the same seeds raises ZERO alerts.
"""
import json
import os

import pytest

from repro import obs
from repro.obs import regress
from repro.obs.collect import load_dir_stats
from repro.obs.health import (ByteDriftDetector, ChainDecayDetector,
                              DivergenceDetector, DPBurnDetector,
                              HealthEngine, RttDetector, StragglerDetector,
                              engine_from_spec)
from repro.obs.monitor import ALERTS_FILE, HEALTH_FILE, MonitorServer
from repro.obs.tracer import Tracer


def _round(m, rnd, dur, wait=None, pid=1000):
    """The two spans one traced party round leaves in the stream (the
    nested wait span ends first, so it arrives first)."""
    out = []
    if wait is not None:
        out.append({"ev": "span", "name": "party_wait_reply", "party": m,
                    "round": rnd, "dur": wait, "pid": pid})
    out.append({"ev": "span", "name": "party_round", "party": m,
                "round": rnd, "dur": dur, "pid": pid})
    return out


def _feed(det, recs):
    alerts = []
    for r in recs:
        alerts.extend(det.feed(r))
    return alerts


# ------------------------------------------------------ straggler ---------

def test_straggler_scores_local_time_so_serial_victims_stay_silent():
    """Under the serial dispatch schedule a 0.3s straggler head-of-line-
    blocks everyone: every party's RAW round duration equalizes at
    ~0.3s. The detector must subtract party_wait_reply and flag exactly
    the party whose time is local (the stall), never the victims whose
    time is waiting."""
    det = StragglerDetector()
    alerts = []
    for rnd in range(8):
        # victim: 0.31s round, 0.30s of it waiting on the server
        alerts += _feed(det, _round(0, rnd, 0.31, wait=0.30, pid=1))
        # straggler: 0.31s round, all of it local stall
        alerts += _feed(det, _round(1, rnd, 0.31, wait=0.001, pid=2))
    assert [a.party for a in alerts] == [1]
    a = alerts[0]
    assert a.detector == "straggler" and a.severity == "warning"
    assert a.value > a.threshold
    assert a.round <= 6            # the e2e latency bound


def test_straggler_silent_on_symmetric_jitter_and_rearms_on_recovery():
    det = StragglerDetector()
    # symmetric microsecond jitter: ratio alone would trip, the absolute
    # min_gap_s floor must not
    alerts = []
    for rnd in range(12):
        alerts += _feed(det, _round(0, rnd, 0.004 + 0.002 * (rnd % 2),
                                    pid=1))
        alerts += _feed(det, _round(1, rnd, 0.005, pid=2))
    assert alerts == []
    # degrade party 0 -> one alert, not one per round
    for rnd in range(12, 20):
        alerts += _feed(det, _round(0, rnd, 0.4, pid=1))
        alerts += _feed(det, _round(1, rnd, 0.005, pid=2))
    assert len(alerts) == 1 and alerts[0].party == 0
    # recover long enough for the EWMA to decay under half the
    # threshold, then degrade again: the episode re-arms and re-fires
    for rnd in range(20, 45):
        alerts += _feed(det, _round(0, rnd, 0.004, pid=1))
        alerts += _feed(det, _round(1, rnd, 0.005, pid=2))
    assert len(alerts) == 1
    for rnd in range(45, 55):
        alerts += _feed(det, _round(0, rnd, 0.4, pid=1))
        alerts += _feed(det, _round(1, rnd, 0.005, pid=2))
    assert len(alerts) == 2


def test_straggler_restarts_warmup_when_party_rejoins_with_new_pid():
    """A rejoined party re-pays jit compilation in its first round. The
    pid change in the record stream must restart the skip_first/warmup
    discipline so the compile spike is skipped, not scored — a crash/
    rejoin run stays alert-free."""
    det = StragglerDetector()
    alerts = []
    for rnd in range(6):
        alerts += _feed(det, _round(0, rnd, 0.005, pid=1))
        alerts += _feed(det, _round(1, rnd, 0.005, pid=2))
    # party 0 crashes and rejoins as pid 3: compile spike, then healthy
    alerts += _feed(det, _round(0, 6, 1.2, pid=3))
    for rnd in range(7, 14):
        alerts += _feed(det, _round(0, rnd, 0.006, pid=3))
        alerts += _feed(det, _round(1, rnd, 0.005, pid=2))
    assert alerts == []


# ----------------------------------------------------- divergence ---------

def test_divergence_nan_fires_critical_once():
    det = DivergenceDetector()
    recs = [{"ev": "gauge", "name": "loss", "value": float("nan"),
             "party": 0, "round": r} for r in range(3)]
    alerts = _feed(det, recs)
    assert len(alerts) == 1
    assert alerts[0].severity == "critical" and alerts[0].party == 0


def test_divergence_trend_needs_patience_and_noise_never_fires():
    det = DivergenceDetector(factor=2.0, patience=3)
    # a noisy but descending ZO trajectory: silent
    noisy = [1.0, 0.9, 1.1, 0.8, 0.95, 0.7, 0.85, 0.6]
    assert _feed(det, [{"ev": "gauge", "name": "loss", "value": v,
                        "party": 0, "round": i}
                       for i, v in enumerate(noisy)]) == []
    # two reads above 2x the min: still silent; the third fires, once
    up = [{"ev": "gauge", "name": "loss", "value": 2.5, "party": 0,
           "round": 10 + i} for i in range(5)]
    alerts = _feed(det, up)
    assert len(alerts) == 1
    assert alerts[0].round == 12      # fired on the 3rd consecutive read
    # metric records carrying the objective h are scored too
    det2 = DivergenceDetector()
    assert len(_feed(det2, [{"ev": "metric", "name": "train",
                             "h": float("inf"), "step": 3}])) == 1


# -------------------------------------------------------- dp burn ---------

def test_dp_burn_overrun_projection_and_calibrated_silence():
    # (a) overrun: cumulative spend past target x 1.02 -> critical, once
    det = DPBurnDetector(target=4.0, expected_releases=100)
    recs = [{"ev": "gauge", "name": "dp_epsilon", "value": v, "party": 0,
             "releases": n} for n, v in [(50, 4.2), (60, 4.3)]]
    alerts = _feed(det, recs)
    assert [a.severity for a in alerts] == ["critical"]
    # (b) projection: linear slope 0.1/release from release 25 lands at
    # 9.5 >> 4.0 x 1.5 -> warning
    det = DPBurnDetector(target=4.0, expected_releases=100)
    recs = [{"ev": "gauge", "name": "dp_epsilon", "value": v, "party": 0,
             "releases": n} for n, v in [(25, 2.0), (30, 2.5)]]
    alerts = _feed(det, recs)
    assert [a.severity for a in alerts] == ["warning"]
    assert alerts[0].value == pytest.approx(9.5)
    # (c) a correctly calibrated concave spend curve (epsilon ~ sqrt(n),
    # landing exactly on target) stays silent: proj_margin absorbs the
    # linear projection's overestimate of a concave curve
    det = DPBurnDetector(target=4.0, expected_releases=100)
    curve = [{"ev": "gauge", "name": "dp_epsilon",
              "value": 4.0 * (n / 100.0) ** 0.5, "party": 0,
              "releases": n} for n in range(1, 101)]
    assert _feed(det, curve) == []
    # (d) no target (undefended / epsilon=inf): never scores
    det = DPBurnDetector(target=None)
    assert _feed(det, recs) == []


# ----------------------------------------------------- byte drift ---------

def test_byte_drift_analytic_and_first_seen_baselines():
    det = ByteDriftDetector(expected={"c_up": 64})
    ok = {"ev": "wire", "kind": "c_up", "nbytes": 64, "sender": "party:0"}
    assert det.feed(ok) == []
    # receiver-side re-accounting duplicates send bytes: skipped
    assert det.feed({**ok, "nbytes": 80, "observed": True}) == []
    alerts = det.feed({**ok, "nbytes": 80, "round": 3})
    assert len(alerts) == 1 and alerts[0].round == 3
    assert det.feed({**ok, "nbytes": 80}) == []      # once per kind
    # unknown kind: first-seen size becomes the baseline
    hb = {"ev": "wire", "kind": "loss_down", "nbytes": 128,
          "sender": "server"}
    assert det.feed(hb) == []
    assert len(det.feed({**hb, "nbytes": 132})) == 1


# ------------------------------------------------------------ rtt ---------

def test_rtt_fires_beyond_baseline_and_absolute_floor():
    det = RttDetector(factor=4.0, min_rtt_s=0.25, baseline_n=3)
    base = [{"ev": "histo", "name": "heartbeat_rtt_s", "peer": "server",
             "value": 0.001} for _ in range(3)]
    assert _feed(det, base) == []
    # 5ms is 5x baseline but under the absolute floor: loopback noise
    assert det.feed({"ev": "histo", "name": "heartbeat_rtt_s",
                     "peer": "server", "value": 0.005}) == []
    alerts = det.feed({"ev": "histo", "name": "heartbeat_rtt_s",
                       "peer": "server", "value": 0.3})
    assert len(alerts) == 1 and alerts[0].severity == "warning"


# ---------------------------------------------------- chain decay ---------

def _chain(m, rnd):
    return [
        {"ev": "span", "name": "party_round", "party": m, "round": rnd},
        {"ev": "wire", "kind": "c_up", "sender": f"party:{m}",
         "round": rnd},
        {"ev": "span", "name": "server_handle", "party": m, "round": rnd},
    ]


def test_chain_decay_settles_then_fires_below_threshold():
    det = ChainDecayDetector(threshold=0.95, settle=2, min_checked=5)
    alerts = []
    for rnd in range(10):
        alerts += _feed(det, _chain(0, rnd))
    assert alerts == []                   # complete chains: silent
    # rounds whose party_round span never arrived: completeness decays
    for rnd in range(10, 20):
        alerts += _feed(det, _chain(0, rnd)[1:])
    assert len(alerts) == 1
    assert alerts[0].value < 0.95


def test_chain_decay_ignores_the_order_streams_are_drained():
    """The collector may drain the server's whole backlog before a
    party's: complete chains stay silent whichever stream comes first."""
    det = ChainDecayDetector(threshold=0.95, settle=2, min_checked=5)
    chains = [_chain(m, rnd) for m in (0, 1) for rnd in range(8)]
    server = [c[2] for c in chains]
    party = [r for c in chains for r in c[:2]]
    assert _feed(det, server + party) == []
    assert det._checked == 2 * (8 - 2)
    assert det._complete == det._checked


def test_chain_decay_fires_when_a_party_stream_goes_silent():
    """A party whose records never arrive has its chains judged once the
    server is ``LAG`` rounds on."""
    det = ChainDecayDetector(threshold=0.95, settle=2, min_checked=5)
    alerts = _feed(det, [_chain(0, rnd)[2] for rnd in range(60)])
    assert len(alerts) == 1
    assert alerts[0].value == 0.0
    # the fifth chain checked (min_checked), judged at server round
    # 4 + settle + LAG = 38
    assert (alerts[0].party, alerts[0].round) == (0, 4)


# --------------------------------------------- engine / spec wiring -------

def _dp_detector(engine):
    return next(d for d in engine.detectors if isinstance(d, DPBurnDetector))


def test_engine_from_spec_derives_dp_target_and_expected_releases():
    spec = {"kind": "lr", "parties": 2, "vfl": {
        "mu": 1e-3, "num_directions": 2,
        "dp": {"epsilon": 4.0, "delta": 1e-5, "clip": 1.0}}}
    det = _dp_detector(engine_from_spec(spec, rounds=10))
    assert det.target == 4.0
    assert det.expected == 10 * (1 + 2)   # one loss + K perturbations
    # epsilon=inf turns DP transparently off: no target, never scores
    off = {"kind": "lr", "parties": 2, "vfl": {
        "dp": {"epsilon": float("inf"), "delta": 1e-5, "clip": 1.0}}}
    assert _dp_detector(engine_from_spec(off, rounds=10)).target is None
    assert _dp_detector(engine_from_spec({"vfl": {}}, 5)).target is None


def test_engine_snapshot_aggregates_per_party_state():
    eng = HealthEngine()
    eng.feed({"ev": "span", "name": "server_handle", "party": 0,
              "round": 4, "ts": 1.0, "dur": 0.001})
    eng.feed({"ev": "gauge", "name": "loss", "value": 0.7, "party": 0,
              "round": 4})
    eng.feed({"ev": "gauge", "name": "dp_epsilon", "value": 1.5,
              "party": 0, "releases": 8})
    snap = eng.snapshot()
    assert snap["records"] == 3 and snap["alerts"] == []
    st = snap["parties"]["0"]
    assert st["rounds"] == 5              # round index 4 -> 5 completed
    assert st["loss"] == pytest.approx(0.7)
    assert st["epsilon"] == pytest.approx(1.5)
    # serving engines drop the byte-drift detector (payloads vary with
    # slot occupancy by design)
    kinds = {type(d) for d in HealthEngine(byte_drift=False).detectors}
    assert ByteDriftDetector not in kinds


# ------------------------------------------- monitor collector e2e --------

def test_monitor_streams_alerts_and_recovers_dirty_disconnect(tmp_path,
                                                              monkeypatch):
    """In-process tentpole e2e: a clean tracer streams and says goodbye
    (no flight file); a crashed tracer — nothing flushed to disk, socket
    dropped without the shutdown frame, exactly what ``os._exit`` leaves
    behind — gets its records recovered from the MONITOR-side ring and
    merged back by collect."""
    mon = MonitorServer(str(tmp_path), engine=HealthEngine())
    monkeypatch.setenv(obs.MONITOR_ENV, mon.addr)

    clean = Tracer(str(tmp_path), role="unit-clean")
    clean.gauge("loss", 1.0, party=0, round=0)
    clean.close()                          # goodbye frame: clean shutdown

    crash = Tracer(str(tmp_path), role="unit-crash", flush_every=10 ** 6)
    for r in range(20):
        crash.gauge("loss", 1.0 - 0.01 * r, party=1, round=r)
    # simulate os._exit: the stream socket dies mid-run, no goodbye, and
    # the buffered records never reach the trace file
    crash._stream.close()

    summary = mon.stop()
    assert summary["records"] >= 21
    assert summary["alerts"] == []
    assert len(summary["flight_files"]) == 1
    assert "unit-crash" in summary["flight_files"][0]
    assert summary == mon.stop()           # idempotent

    records, stats = load_dir_stats(str(tmp_path))
    assert stats["flight_files"] == 1
    assert stats["flight_recovered"] == 20      # every otherwise-lost rec
    lost = [r for r in records if r.get("role") == "unit-crash"]
    assert {r["round"] for r in lost} == set(range(20))

    assert os.path.exists(tmp_path / ALERTS_FILE)
    doc = json.loads((tmp_path / HEALTH_FILE).read_text())
    assert doc["live"] is False
    assert doc["snapshot"]["records"] == summary["records"]


def test_monitor_writes_alert_log_with_identity(tmp_path, monkeypatch):
    mon = MonitorServer(str(tmp_path), engine=HealthEngine(
        detectors=[DivergenceDetector()]))
    monkeypatch.setenv(obs.MONITOR_ENV, mon.addr)
    t = Tracer(str(tmp_path), role="unit-diverge")
    t.gauge("loss", float("nan"), party=1, round=7)
    t.close()
    summary = mon.stop()
    assert len(summary["alerts"]) == 1
    lines = [json.loads(ln) for ln in
             (tmp_path / ALERTS_FILE).read_text().splitlines()]
    assert len(lines) == 1
    a = lines[0]
    assert a["detector"] == "divergence" and a["severity"] == "critical"
    assert a["party"] == 1 and a["round"] == 7
    assert a["role"] == "unit-diverge" and "ts_unix" in a


def test_tracer_survives_dead_and_absent_monitor(tmp_path, monkeypatch):
    """Silent degradation: a bogus collector address must not break the
    run — the tracer drops the stream and keeps writing its file."""
    monkeypatch.setenv(obs.MONITOR_ENV, "127.0.0.1:1")   # nothing listens
    t = Tracer(str(tmp_path), role="unit-nostream")
    t.gauge("loss", 0.5, party=0, round=0)
    t.close()
    records, stats = load_dir_stats(str(tmp_path))
    assert stats["records"] == 1 and records[0]["value"] == 0.5


# ------------------------------------- collect hardening + live view ------

def test_collect_skips_torn_trailing_line_and_counts_it(tmp_path):
    """Satellite: a process killed mid-write leaves a truncated final
    JSONL line; the merge must skip it, count it, and keep every intact
    record."""
    t = Tracer(str(tmp_path), role="unit-torn")
    for r in range(5):
        t.gauge("loss", 1.0, party=0, round=r)
    t.close()
    (path,) = list(tmp_path.glob("trace-*.jsonl"))
    with open(path, "a") as f:
        f.write('{"ev": "gauge", "name": "loss", "va')   # torn mid-key
    records, stats = load_dir_stats(str(tmp_path))
    assert stats["dropped_lines"] == 1
    assert len([r for r in records if r["ev"] == "gauge"]) == 5


def test_live_snapshot_renders_party_table_and_alerts(tmp_path, capsys):
    from repro.obs import live
    t = Tracer(str(tmp_path), role="fed-party0")
    for r in range(3):
        with t.span("party_round", party=0, round=r):
            pass
        with t.span("server_handle", party=0, round=r):
            pass
    t.gauge("loss", float("nan"), party=0, round=2)
    t.close()
    rc = live.main([str(tmp_path), "--snapshot"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "federation health" in out
    assert "divergence" in out and "party=0" in out
    # an empty dir renders, but exits non-zero so scripts can tell
    empty = tmp_path / "empty"
    empty.mkdir()
    assert live.main([str(empty), "--snapshot"]) == 1


# ------------------------------------------------- bench regression -------

def _bench(tmp_path, subdir, name, rows, ok=True):
    d = tmp_path / subdir
    d.mkdir(exist_ok=True)
    doc = {"artifact": name, "ok": ok,
           "rows": [{"name": n, "metrics": m} for n, m in rows.items()]}
    (d / f"BENCH_{name}.json").write_text(json.dumps(doc))
    return str(d)


def test_regress_passes_identical_and_tolerated_drift(tmp_path):
    rows = {"parity": {"equal": 1.0}, "chain": {"fraction": 0.99},
            "fused": {"overhead_pct": 1.0, "pass": 1.0}}
    base = _bench(tmp_path, "base", "x", rows)
    fresh_rows = {"parity": {"equal": 1.0}, "chain": {"fraction": 0.98},
                  "fused": {"overhead_pct": 2.5, "pass": 1.0}}
    fresh = _bench(tmp_path, "fresh", "x", fresh_rows)
    assert regress.main(["--baseline", base, "--fresh", fresh]) == 0


def test_regress_fails_on_gate_row_and_tolerance_regressions(tmp_path):
    rows = {"parity": {"equal": 1.0}, "chain": {"fraction": 0.99},
            "fused": {"overhead_pct": 1.0}}
    base = _bench(tmp_path, "base", "x", rows)
    # gate 1 -> 0, a vanished row, and drifts past both tolerances
    fresh = _bench(tmp_path, "fresh", "x",
                   {"parity": {"equal": 0.0},
                    "chain": {"fraction": 0.90},
                    "fused": {"overhead_pct": 3.5}})
    assert regress.main(["--baseline", base, "--fresh", fresh]) == 1
    gone = _bench(tmp_path, "fresh2", "x", {"parity": {"equal": 1.0}})
    assert regress.main(["--baseline", base, "--fresh", gone]) == 1


def test_regress_missing_artifacts_and_empty_baseline(tmp_path):
    base = _bench(tmp_path, "base", "x", {"parity": {"equal": 1.0}})
    nofresh = tmp_path / "nofresh"
    nofresh.mkdir()
    assert regress.main(["--baseline", base,
                         "--fresh", str(nofresh)]) == 1
    empty = tmp_path / "emptybase"
    empty.mkdir()
    assert regress.main(["--baseline", str(empty)]) == 2
