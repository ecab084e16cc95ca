"""Observability invariants (docs/observability.md): tracing is
bitwise-invisible on every transport, the per-process trace files merge
deterministically into a valid Chrome trace, and the federation's
chains reconstruct from the merged record.

The parity tests are the tentpole: a traced run must equal an untraced
run bit-for-bit — losses, final parameters, per-kind wire bytes, and
(over TCP) the measured socket bytes — because the tracer only ever
reads clocks and writes its own files. PR 10 extends the same bar to
the LIVE plane: a ``--monitor`` run (records mirrored over a side
socket to the collector, online detectors armed) must hold the exact
same equalities, ride zero protocol Messages, recover a crashed
party's final rounds from the collector-side flight ring, and alert on
an injected straggler while staying silent on a clean run.
"""
import io
import json
import os
import re

import numpy as np
import pytest

from repro import obs
from repro.configs.base import RuntimeConfig
from repro.core.wire import RecordingChannel
from repro.obs.collect import (chain_completeness, chrome_trace, load_dir,
                               load_dir_stats, summary)
from repro.obs.tracer import Tracer
from repro.runtime import (FailurePlan, PartyFault, history_losses,
                           run_federation, run_reference)

runtime = pytest.mark.runtime
slow = pytest.mark.slow

DELTA = 1e-5


def _spec(**vfl):
    base = {"mu": 1e-3, "lr_party": 1e-2, "lr_server": 1e-3}
    base.update(vfl)
    return {"kind": "lr", "parties": 2, "features": 16, "samples": 64,
            "batch": 8, "seed": 0, "vfl": base}


def _cfg(**kw):
    kw.setdefault("deadline_s", 120.0)
    return RuntimeConfig(**kw)


def _traced_reference(spec, rounds, trace_dir, channel=None):
    obs.configure(str(trace_dir), role="main")
    try:
        return run_reference(spec, rounds, channel=channel)
    finally:
        obs.configure(None)


# ------------------------------------ acceptance: traced == untraced ------

def test_traced_memory_run_bit_identical_to_untraced(tmp_path):
    """The headline invariant on the in-memory path: tracing on changes
    not one bit of the trajectory, the final parameters, or the per-kind
    wire accounting — the tracer never touches an RNG stream, a payload,
    or wire_nbytes."""
    spec, rounds = _spec(), 5
    rec0, rec1 = RecordingChannel(), RecordingChannel()
    tr0, res0 = run_reference(spec, rounds, channel=rec0)
    tr1, res1 = _traced_reference(spec, rounds, tmp_path, channel=rec1)

    assert [h for _, h in res0.history] == [h for _, h in res1.history]
    assert dict(rec0.bytes_by_kind) == dict(rec1.bytes_by_kind)
    assert dict(rec0.msgs_by_kind) == dict(rec1.msgs_by_kind)
    # the recorded transcripts agree message by message (RecordingChannel
    # equality covers kind/sender/receiver/round/payload/meta)
    assert len(rec0.transcript) == len(rec1.transcript)
    assert dict(rec0.transcript.bytes_by_kind()) == \
        dict(rec1.transcript.bytes_by_kind())
    for m in range(2):
        np.testing.assert_array_equal(np.asarray(tr0.party_w[m]["w"]),
                                      np.asarray(tr1.party_w[m]["w"]))
    np.testing.assert_array_equal(np.asarray(tr0.server.w0["b"]),
                                  np.asarray(tr1.server.w0["b"]))
    # and the trace actually captured the run
    recs = load_dir(str(tmp_path))
    assert recs, "traced run produced no records"


def test_traced_defended_fused_run_bit_identical_and_budget_held(tmp_path):
    """Parity extends to the hardest path — DP noise + the fused-kernel
    fast path — and the tracer's shadow accountant lands exactly on the
    calibrated per-party budget at the final round (same accountant,
    same curve, so the trace's epsilon IS the spend, inside the
    sigma-calibration tolerance)."""
    eps_target, rounds = 4.0, 6
    spec = _spec(mu=5e-2, fused=True,
                 dp={"epsilon": eps_target, "delta": DELTA, "clip": 1.0})
    tr0, res0 = run_reference(spec, rounds)
    tr1, res1 = _traced_reference(spec, rounds, tmp_path)

    assert [h for _, h in res0.history] == [h for _, h in res1.history]
    for m in range(2):
        np.testing.assert_array_equal(np.asarray(tr0.party_w[m]["w"]),
                                      np.asarray(tr1.party_w[m]["w"]))

    recs = load_dir(str(tmp_path))
    eps = {}
    for r in recs:                     # time-sorted: last value wins
        if r["ev"] == "gauge" and r["name"] == "dp_epsilon":
            eps[r["party"]] = r["value"]
    assert set(eps) == {0, 1}          # per-party ledgers, not pooled
    for m, e in eps.items():
        assert 0.95 * eps_target <= e <= eps_target + 1e-9, (m, e)


# ------------------------------------------- merge / export mechanics -----

_VOLATILE = ("ts", "dur", "unix", "pid", "tid", "t0_unix", "t0_mono")


def _normalized(trace_dir):
    out = []
    for r in load_dir(str(trace_dir)):
        out.append(json.dumps({k: v for k, v in r.items()
                               if k not in _VOLATILE}, sort_keys=True))
    return sorted(out)


def test_trace_merge_is_deterministic_across_runs(tmp_path):
    """Two traced runs of the same spec produce the same merged record
    set once wall-clock fields are stripped: every span/wire/gauge
    identity (name, party, round, kind, nbytes, epsilon...) is a pure
    function of the run, only the timestamps are the machine's."""
    spec, rounds = _spec(), 4
    _traced_reference(spec, rounds, tmp_path / "a")
    _traced_reference(spec, rounds, tmp_path / "b")
    assert _normalized(tmp_path / "a") == _normalized(tmp_path / "b")


def test_chrome_trace_schema_is_valid(tmp_path):
    _traced_reference(_spec(), 3, tmp_path)
    doc = chrome_trace(load_dir(str(tmp_path)))
    events = doc["traceEvents"]
    assert events
    pids_named = set()
    for ev in events:
        assert ev["ph"] in ("X", "C", "i", "M")
        assert isinstance(ev["pid"], int)
        assert isinstance(ev["name"], str) and ev["name"]
        if ev["ph"] == "M":
            assert ev["name"] == "process_name"
            pids_named.add(ev["pid"])
        else:
            assert ev["ts"] >= 0.0
        if ev["ph"] == "X":
            assert ev["dur"] >= 0.0
        if ev["ph"] == "C":
            (val,) = ev["args"].values()
            assert isinstance(val, (int, float))
    # every pid that emitted an event carries a process_name record
    assert {ev["pid"] for ev in events} == pids_named
    json.dumps(doc)                    # serializable end to end


def test_summary_renders_spans_and_chains(tmp_path):
    _traced_reference(_spec(), 3, tmp_path)
    text = summary(load_dir(str(tmp_path)))
    assert "party_round" in text and "server_handle" in text
    assert "complete party->wire->server chains" in text


def test_chain_completeness_counts_missing_links_against_total():
    recs = [
        {"ev": "span", "name": "party_round", "party": 0, "round": 0},
        {"ev": "wire", "kind": "c_up", "sender": "party:0", "round": 0},
        {"ev": "span", "name": "server_handle", "party": 0, "round": 0},
        # round 1: the server span never made it to disk
        {"ev": "span", "name": "party_round", "party": 0, "round": 1},
        {"ev": "wire", "kind": "c_up", "sender": "party:0", "round": 1},
    ]
    complete, total, frac = chain_completeness(recs)
    assert (complete, total) == (1, 2) and frac == 0.5


def test_memory_run_reconstructs_every_round_chain(tmp_path):
    """ISSUE acceptance (in-memory floor): >=95% of rounds reconstruct a
    complete party->wire->server chain from the merged trace."""
    rounds = 6
    _traced_reference(_spec(), rounds, tmp_path)
    complete, total, frac = chain_completeness(load_dir(str(tmp_path)))
    assert total == 2 * rounds         # every (party, round) was seen
    assert frac >= 0.95


# ------------------------------------------------- tracer unit seams ------

def test_heartbeat_rtt_fifo_matches_pings_in_order(tmp_path):
    t = Tracer(str(tmp_path), role="unit")
    t.ping_sent("server")
    t.ping_sent("server")
    t.pong_received("server")
    t.pong_received("server")
    t.pong_received("server")          # unmatched: dropped, not lied
    t.close()
    recs = load_dir(str(tmp_path))
    rtts = [r for r in recs
            if r["ev"] == "histo" and r["name"] == "heartbeat_rtt_s"]
    assert len(rtts) == 2
    assert all(r["peer"] == "server" and r["value"] >= 0.0 for r in rtts)


def test_metric_logger_printed_line_is_byte_identical(tmp_path):
    """Satellite: launch/train.py now logs through ObsMetricLogger —
    the human-facing line must be byte-identical to the plain
    MetricLogger (modulo the elapsed-seconds token), tracing on or off,
    so every existing log scrape keeps parsing."""
    from repro.obs.metrics import ObsMetricLogger
    from repro.utils.logging import MetricLogger

    def line(logger_cls, stream):
        lg = logger_cls("train", stream=stream)
        lg.log(3, loss=0.123456789, lr=1e-2, note="warmup")
        return re.sub(r"t=\d+\.\d\ds", "t=<T>s", stream.getvalue())

    plain = line(MetricLogger, io.StringIO())
    obs.configure(None)                          # tracing off
    assert line(ObsMetricLogger, io.StringIO()) == plain
    obs.configure(str(tmp_path), role="launch")  # tracing on
    try:
        assert line(ObsMetricLogger, io.StringIO()) == plain
    finally:
        obs.configure(None)
    metrics = [r for r in load_dir(str(tmp_path)) if r["ev"] == "metric"]
    assert len(metrics) == 1
    m = metrics[0]
    assert m["name"] == "train" and m["step"] == 3
    assert m["loss"] == pytest.approx(0.123456789)
    assert m["note"] == "warmup"


def test_trace_off_is_a_shared_noop_and_env_configures_children(tmp_path,
                                                                monkeypatch):
    obs.configure(None)
    assert obs.maybe_tracer() is None
    assert obs.trace("x") is obs.trace("y")      # one cached null span
    # a process that was never configured resolves REPRO_TRACE_DIR once
    monkeypatch.setenv(obs.ENV_VAR, str(tmp_path))
    import repro.obs as obs_mod
    monkeypatch.setattr(obs_mod, "_tracer", obs_mod._UNSET)
    t = obs.maybe_tracer()
    try:
        assert t is not None and str(tmp_path) in t.path
    finally:
        obs.configure(None)


# ---------------------------------------- acceptance over real sockets ----

@runtime
@slow
def test_traced_tcp_run_bit_identical_and_chains_reconstruct(tmp_path):
    """The full-stack acceptance: a traced TCP federation equals an
    untraced one bit-for-bit — losses, final params, per-kind payload
    bytes AND measured socket bytes (tracing adds zero wire traffic) —
    and the merged per-process trace reconstructs >=95% of round chains
    across the party -> wire -> server process boundary."""
    spec, rounds = _spec(), 4
    res_u = run_federation(spec, rounds, cfg=_cfg())
    res_t = run_federation(spec, rounds,
                           cfg=_cfg(trace_dir=str(tmp_path)))

    np.testing.assert_array_equal(history_losses(res_u),
                                  history_losses(res_t))
    assert res_u["server"]["bytes_by_kind"] == res_t["server"]["bytes_by_kind"]
    # the ping/pong count follows the wall clock, not the seed: the
    # control frames' bytes are left out (transport.byte_counts)
    assert res_u["server"]["protocol_bytes_in"] == \
        res_t["server"]["protocol_bytes_in"]
    assert res_u["server"]["protocol_bytes_out"] == \
        res_t["server"]["protocol_bytes_out"]
    for m in range(2):
        np.testing.assert_array_equal(res_u["parties"][m]["final_w"]["w"],
                                      res_t["parties"][m]["final_w"]["w"])

    recs = load_dir(str(tmp_path))
    roles = {r["role"] for r in recs}
    assert "fed-server" in roles
    assert {"fed-party0", "fed-party1"} <= roles
    complete, total, frac = chain_completeness(recs)
    assert total >= 2 * rounds
    assert frac >= 0.95, (complete, total)
    # the wire records crossed a REAL process boundary yet still join
    kinds = {r["kind"] for r in recs if r["ev"] == "wire"}
    assert {"c_up", "c_hat_up", "loss_down"} <= kinds
    # single-counting: each crossing is traced at BOTH endpoints (send +
    # observe); the send-side records alone reproduce the federation's
    # per-kind byte accounting exactly
    sent = {}
    for r in recs:
        if r["ev"] == "wire" and not r["observed"]:
            sent[r["kind"]] = sent.get(r["kind"], 0) + r["nbytes"]
    assert sent == res_t["server"]["bytes_by_kind"]


@runtime
@slow
def test_arrival_schedule_traces_staleness_and_parking(tmp_path):
    """Under the arrival schedule with a straggler and tau=1, the trace
    records what the server actually did: a staleness sample at every
    admission (none above tau) and a parked-duration sample for each
    round the bound held back."""
    spec, rounds = _spec(), 5
    plan = FailurePlan({1: PartyFault(slow_send_s=0.25)})
    res = run_federation(spec, rounds, plan=plan,
                         cfg=_cfg(schedule="arrival", max_staleness=1,
                                  trace_dir=str(tmp_path)))
    assert res["server"]["parked"] > 0
    recs = load_dir(str(tmp_path))
    stale = [r for r in recs
             if r["ev"] == "histo" and r["name"] == "staleness"]
    parked = [r for r in recs
              if r["ev"] == "histo" and r["name"] == "parked_s"]
    assert len(stale) == res["server"]["updates"]
    assert max(r["value"] for r in stale) <= 1
    assert len(parked) == res["server"]["parked"]
    assert all(r["value"] > 0.0 for r in parked)


# ----------------------------------- live plane: monitored == plain -------

def _monitored_reference(spec, rounds, trace_dir, channel=None):
    """Run the in-memory reference with the FULL live plane armed: a
    parent-side collector, the tracer streaming every record to it over
    the side socket, and the spec-tuned detectors scoring online."""
    from repro.obs.health import engine_from_spec
    from repro.obs.monitor import MonitorServer
    monitor = MonitorServer(str(trace_dir),
                            engine=engine_from_spec(spec, rounds))
    os.environ[obs.MONITOR_ENV] = monitor.addr
    try:
        out = _traced_reference(spec, rounds, trace_dir, channel=channel)
    finally:
        os.environ.pop(obs.MONITOR_ENV, None)
    return out, monitor.stop()


def test_monitored_memory_run_bit_identical_and_alert_free(tmp_path):
    """ISSUE acceptance (memory transport, hardest path: DP noise + the
    fused kernels): arming the monitor changes not one bit — losses,
    final params, per-kind wire bytes, message counts — and the online
    detectors (including the DP burn detector against the real
    accountant curve) raise ZERO alerts on a clean run."""
    spec, rounds = _spec(mu=5e-2, fused=True,
                         dp={"epsilon": 4.0, "delta": DELTA,
                             "clip": 1.0}), 6
    rec0, rec1 = RecordingChannel(), RecordingChannel()
    tr0, res0 = run_reference(spec, rounds, channel=rec0)
    (tr1, res1), summ = _monitored_reference(spec, rounds, tmp_path,
                                             channel=rec1)
    assert [h for _, h in res0.history] == [h for _, h in res1.history]
    assert dict(rec0.bytes_by_kind) == dict(rec1.bytes_by_kind)
    assert dict(rec0.msgs_by_kind) == dict(rec1.msgs_by_kind)
    for m in range(2):
        np.testing.assert_array_equal(np.asarray(tr0.party_w[m]["w"]),
                                      np.asarray(tr1.party_w[m]["w"]))
    # the collector actually saw the run, scored it, and stayed silent
    assert summ["records"] > 0
    assert summ["alerts"] == []
    assert summ["flight_files"] == []      # clean close: goodbye frames
    assert (tmp_path / "health.json").exists()
    assert (tmp_path / "alerts.jsonl").read_text() == ""


@runtime
@slow
def test_monitored_tcp_run_bit_identical_and_out_of_band(tmp_path):
    """ISSUE acceptance (tcp): a ``monitor=True`` federation equals the
    unmonitored one on losses, params, per-kind wire bytes, message
    counts AND measured socket bytes — the telemetry stream rides zero
    protocol Messages and zero protocol-socket bytes, with DP noise and
    the fused kernels on."""
    spec, rounds = _spec(mu=5e-2, fused=True,
                         dp={"epsilon": 4.0, "delta": DELTA,
                             "clip": 1.0}), 4
    res_u = run_federation(spec, rounds, cfg=_cfg())
    res_m = run_federation(spec, rounds,
                           cfg=_cfg(trace_dir=str(tmp_path), monitor=True))
    np.testing.assert_array_equal(history_losses(res_u),
                                  history_losses(res_m))
    srv_u, srv_m = res_u["server"], res_m["server"]
    assert srv_u["bytes_by_kind"] == srv_m["bytes_by_kind"]
    assert srv_u["msgs_by_kind"] == srv_m["msgs_by_kind"]
    # protocol frames only: a party pings after heartbeat_s of silence,
    # so the control frames' count depends on load, not on the monitor
    assert srv_u["protocol_bytes_in"] == srv_m["protocol_bytes_in"]
    assert srv_u["protocol_bytes_out"] == srv_m["protocol_bytes_out"]
    for m in range(2):
        np.testing.assert_array_equal(res_u["parties"][m]["final_w"]["w"],
                                      res_m["parties"][m]["final_w"]["w"])
    mon = res_m["monitor"]
    assert mon["records"] > 0 and mon["alerts"] == []
    assert (tmp_path / "health.json").exists()


@runtime
@slow
def test_straggler_alert_within_bound_and_clean_run_silent(tmp_path):
    """Satellite e2e: a PartyFault(slow_send_s=0.3) on party 1 raises a
    straggler alert naming that party within 6 rounds; the identical
    federation without the fault — same spec, same seeds — raises ZERO
    alerts."""
    spec, rounds = _spec(), 8
    res = run_federation(
        spec, rounds, plan=FailurePlan({1: PartyFault(slow_send_s=0.3)}),
        cfg=_cfg(trace_dir=str(tmp_path / "slow"), monitor=True))
    alerts = res["monitor"]["alerts"]
    stragglers = [a for a in alerts if a["detector"] == "straggler"]
    assert stragglers, f"no straggler alert in {alerts}"
    first = stragglers[0]
    assert first["party"] == 1
    assert first["round"] <= 6
    # every line in the on-disk log carries the same identity
    logged = [json.loads(ln) for ln in
              (tmp_path / "slow" / "alerts.jsonl").read_text().splitlines()]
    assert any(a["detector"] == "straggler" and a["party"] == 1
               for a in logged)

    clean = run_federation(
        spec, rounds, cfg=_cfg(trace_dir=str(tmp_path / "clean"),
                               monitor=True))
    assert clean["monitor"]["alerts"] == []
    assert (tmp_path / "clean" / "alerts.jsonl").read_text() == ""


@runtime
@slow
def test_flight_recorder_survives_os_exit_crash(tmp_path):
    """ISSUE acceptance: party 0 dies by ``os._exit`` (no atexit, no
    signal handler, nothing flushed) mid-federation. The monitor-side
    ring must recover its final pre-crash rounds into the merged trace
    and the Perfetto export — the crashed pid's party_round spans are
    all there."""
    spec, rounds, crash_at = _spec(), 6, 3
    res = run_federation(
        spec, rounds,
        plan=FailurePlan({0: PartyFault(crash_at_round=crash_at)}),
        cfg=_cfg(trace_dir=str(tmp_path), monitor=True),
        ckpt_root=str(tmp_path / "ckpt"))
    assert res["rejoins"] == 1
    flights = res["monitor"]["flight_files"]
    assert len(flights) == 1
    fname = os.path.basename(flights[0])
    assert fname.startswith("flight-fed-party0-")
    crashed_pid = int(fname.split("-")[3].split(".")[0])

    records, stats = load_dir_stats(str(tmp_path))
    assert stats["flight_files"] == 1
    assert stats["flight_recovered"] > 0, \
        "every flight record was already on disk — recorder proved nothing"
    pre_crash = {r["round"] for r in records
                 if r.get("pid") == crashed_pid and r["ev"] == "span"
                 and r["name"] == "party_round"}
    assert pre_crash == set(range(crash_at)), \
        f"killed party's final rounds missing: {sorted(pre_crash)}"
    # and they survive into the Chrome/Perfetto export
    doc = chrome_trace(records)
    ev_rounds = {ev["args"].get("round") for ev in doc["traceEvents"]
                 if ev.get("ph") == "X" and ev["pid"] == crashed_pid
                 and ev["name"] == "party_round"}
    assert ev_rounds == set(range(crash_at))


# ------------------------------------------------------- bench smoke ------

@slow
def test_overhead_bench_smoke():
    """BENCH_obs.json's generator runs end to end at toy scale and its
    rows carry the overhead-gate fields CI publishes."""
    from benchmarks import bench_obs
    rows = bench_obs.run(rounds=3, reps=1, tcp=False)
    names = [r[0] for r in rows]
    assert "fused_round_untraced" in names
    assert "fused_round_traced" in names
    assert "overhead_pct" in rows[names.index("fused_round_traced")][2]
    parity = rows[names.index("traced_equals_untraced")]
    assert "equal=1" in parity[2]
    # the full live plane rides the same run shape: collector armed,
    # records collected, a healthy toy run raises zero alerts
    monitored = rows[names.index("monitored_overhead")][2]
    assert "overhead_pct" in monitored
    assert "healthy=1" in monitored
    # the fault-injection rows need real processes: tcp runs only
    assert "alert_latency" not in names
    assert "flight_recorder_coverage" not in names
