"""The multi-process federation runtime: TCP transport framing, the
serial-schedule bit-identity acceptance against the in-memory executor,
checkpointed crash+rejoin recovery, and the arrival (async) schedule
under scripted faults.

Everything spawning real OS processes is marked ``runtime`` (and
``slow``): CI runs them in a dedicated job with a hard timeout and
orphan cleanup (`pytest -m runtime`).
"""
import socket

import numpy as np
import pytest

from repro.configs.base import RuntimeConfig
from repro.core.wire import SERVER, Message, RecordingChannel, party
from repro.runtime import (FailurePlan, PartyFault, TransportTimeout,
                           FramedSocket, history_losses, run_federation,
                           run_reference)
from repro.runtime.transport import byte_counts, encode_message

runtime = pytest.mark.runtime
slow = pytest.mark.slow


def _spec(**vfl):
    base = {"mu": 1e-3, "lr_party": 1e-2, "lr_server": 1e-3}
    base.update(vfl)
    return {"kind": "lr", "parties": 2, "features": 16, "samples": 64,
            "batch": 8, "seed": 0, "vfl": base}


def _cfg(**kw):
    kw.setdefault("deadline_s", 120.0)
    return RuntimeConfig(**kw)


# ------------------------------------------------------- framing (no mp) --

def _socketpair():
    a, b = socket.socketpair()
    return FramedSocket(a), FramedSocket(b)


def test_framed_socket_roundtrips_messages_and_controls():
    a, b = _socketpair()
    msg = Message.make("c_up", party(0), SERVER, 2,
                       np.arange(6, dtype=np.float32),
                       meta={"idx": np.arange(6), "dir": 0})
    a.send_message(msg)
    a.send_control({"type": "ping"})
    kind, got = b.recv(timeout=5.0)
    assert kind == "msg"
    assert (got.kind, got.sender, got.round, got.nbytes) == \
        ("c_up", party(0), 2, 24)
    np.testing.assert_array_equal(got.payload, msg.payload)
    np.testing.assert_array_equal(got.meta["idx"], msg.meta["idx"])
    kind, got = b.recv(timeout=5.0)
    assert kind == "ctl" and got == {"type": "ping"}
    # measured socket bytes cover framing overhead on top of the payload
    assert a.bytes_out == b.bytes_in > msg.nbytes
    a.close(), b.close()


def test_framed_socket_counts_control_frames_apart():
    a, b = _socketpair()
    msg = Message.make("loss_down", SERVER, party(1), 3, (0.5, 0.25))
    for _ in range(3):
        a.send_control({"type": "ping"})
    a.send_message(msg)
    a.send_control({"type": "bye"})
    kinds = [b.recv(timeout=5.0)[0] for _ in range(5)]
    assert kinds == ["ctl"] * 3 + ["msg", "ctl"]
    ctl = 3 * (5 + len(b'{"type": "ping"}')) + 5 + len(b'{"type": "bye"}')
    assert a.control_bytes_out == b.control_bytes_in == ctl
    # the protocol frames alone: the message's frame, whatever the pings
    sent, got = byte_counts([a]), byte_counts([b])
    assert sent["protocol_bytes_out"] == got["protocol_bytes_in"] == \
        len(encode_message(msg)) + 5
    assert sent["socket_bytes_out"] == got["socket_bytes_in"] == \
        a.bytes_out
    a.close(), b.close()


def test_framed_socket_timeout_is_typed():
    a, b = _socketpair()
    with pytest.raises(TransportTimeout):
        b.recv(timeout=0.05)
    a.close(), b.close()


def test_recv_survives_mid_frame_timeout():
    """A timeout with a frame partially received must not desynchronize
    the stream: the retried recv() resumes the SAME frame."""
    a, b = _socketpair()
    msg = Message.make("c_up", party(0), SERVER, 0,
                       np.arange(16, dtype=np.float32))
    body = encode_message(msg)
    import struct
    frame = struct.pack(">I", len(body) + 1) + b"\x00" + body
    a.sock.sendall(frame[:11])                  # header + a few bytes
    with pytest.raises(TransportTimeout):
        b.recv(timeout=0.05)
    a.sock.sendall(frame[11:])                  # the rest arrives late
    kind, got = b.recv(timeout=5.0)
    assert kind == "msg"
    np.testing.assert_array_equal(got.payload, msg.payload)
    a.close(), b.close()


# ------------------------------------- acceptance: TCP == memory, bitwise --

@runtime
@slow
def test_tcp_run_bit_identical_to_inmemory_reference():
    """A fixed-seed 2-party run over the TCP transport reproduces the
    in-memory InMemoryChannel loss trajectory BIT-identically, and a
    RecordingChannel stacked on the TCP transport yields the same
    per-kind byte accounting and transcript as the simulated path."""
    spec, rounds = _spec(), 5
    res = run_federation(spec, rounds, cfg=_cfg(),
                         channel_kind="recording")
    rec = RecordingChannel()
    tr, ref = run_reference(spec, rounds, channel=rec)

    np.testing.assert_array_equal(
        history_losses(res), np.asarray([h for _, h in ref.history]))
    # wire accounting: channel counters AND recorded transcript agree
    # with the single-process path, kind by kind
    assert res["server"]["bytes_by_kind"] == dict(rec.bytes_by_kind)
    assert res["server"]["msgs_by_kind"] == dict(rec.msgs_by_kind)
    assert res["server"]["transcript_bytes_by_kind"] == \
        dict(rec.transcript.bytes_by_kind())
    assert res["server"]["transcript_len"] == len(rec.transcript)
    # every endpoint ends at the same parameters
    for m in range(2):
        np.testing.assert_array_equal(res["parties"][m]["final_w"]["w"],
                                      np.asarray(tr.party_w[m]["w"]))
    np.testing.assert_array_equal(res["server"]["w0"]["b"],
                                  np.asarray(tr.server.w0["b"]))
    # the actual socket bytes exceed payload bytes (framing overhead) but
    # every frame's payload span was validated against wire_nbytes
    total_payload = sum(res["server"]["bytes_by_kind"].values())
    assert res["server"]["socket_bytes_in"] > 0
    assert (res["server"]["socket_bytes_in"]
            + res["server"]["socket_bytes_out"]) > total_payload


@runtime
@slow
def test_int8_codec_rides_the_tcp_transport():
    spec, rounds = _spec(codec="int8"), 3
    res = run_federation(spec, rounds, cfg=_cfg())
    _, ref = run_reference(spec, rounds)
    np.testing.assert_array_equal(
        history_losses(res), np.asarray([h for _, h in ref.history]))
    # int8 wire: (batch + 4 scale) bytes per c payload
    assert res["server"]["bytes_by_kind"]["c_up"] == rounds * 2 * (8 + 4)


# ------------------------------------------- crash + checkpointed rejoin --

@runtime
@slow
def test_party_crash_rejoin_resumes_losslessly(tmp_path):
    """Scripted crash at round 3 + delayed rejoin: the rejoined party
    restores from its latest checkpoint, replays its RNG, and the
    federation reproduces the no-fault trajectory bit-for-bit (the
    paper's losslessness claim, across a real process boundary)."""
    spec, rounds = _spec(lr_party=5e-2, lr_server=1e-2), 6
    ok = run_federation(spec, rounds, cfg=_cfg(),
                        ckpt_root=str(tmp_path / "ok"))
    plan = FailurePlan({1: PartyFault(crash_at_round=3,
                                      rejoin_delay_s=0.3)})
    crashed = run_federation(spec, rounds, cfg=_cfg(), plan=plan,
                             ckpt_root=str(tmp_path / "crash"))
    assert crashed["rejoins"] == 1
    assert crashed["server"]["disconnects"] == 1
    np.testing.assert_array_equal(history_losses(ok),
                                  history_losses(crashed))
    for m in range(2):
        np.testing.assert_array_equal(ok["parties"][m]["final_w"]["w"],
                                      crashed["parties"][m]["final_w"]["w"])
    # the membership change snapshotted server state through
    # repro.checkpoint (plus the final run-complete snapshot)
    from repro.checkpoint import latest_step, load_metadata
    step = latest_step(str(tmp_path / "crash" / "server"))
    assert step == crashed["server"]["updates"]
    assert load_metadata(str(tmp_path / "crash" / "server"),
                         step)["updates"] == step
    # the crashed party resumed from its own checkpoint dir
    assert latest_step(str(tmp_path / "crash" / "party1")) == rounds


@runtime
@slow
def test_federation_stop_and_resume_is_bitwise_continuous(tmp_path):
    """Elastic resume of the WHOLE federation: run 3 rounds with
    checkpointing, restart every process with resume=True for 6, and
    the stitched trajectory equals one uninterrupted 6-round run
    bit-for-bit (server restores w0/c_table/update-count + reply cache,
    parties restore their blocks and fast-forward their RNG streams)."""
    spec = _spec()
    cont = run_federation(spec, 6, cfg=_cfg())
    root = str(tmp_path / "ck")
    first = run_federation(spec, 3, cfg=_cfg(), ckpt_root=root)
    second = run_federation(spec, 6, cfg=_cfg(), ckpt_root=root,
                            resume=True)
    stitched = np.concatenate([history_losses(first),
                               history_losses(second)])
    np.testing.assert_array_equal(stitched, history_losses(cont))
    for m in range(2):
        np.testing.assert_array_equal(
            cont["parties"][m]["final_w"]["w"],
            second["parties"][m]["final_w"]["w"])


@runtime
@slow
def test_resume_replays_rounds_behind_server_from_persisted_cache(tmp_path):
    """A party whose checkpoint lags the server's progress (here: its
    newest checkpoint is deleted between runs, standing in for a kill
    inside the process-round/checkpoint window) replays an
    already-processed round on resume; the server answers it from the
    PERSISTED reply cache without advancing state, and the stitched
    trajectory still equals the uninterrupted run."""
    import os

    spec = _spec()
    cont = run_federation(spec, 5, cfg=_cfg())
    root = str(tmp_path / "ck")
    first = run_federation(spec, 3, cfg=_cfg(), ckpt_root=root)
    for m in range(2):                   # drop every party's newest step
        for suffix in ("npz", "json"):
            os.remove(os.path.join(root, f"party{m}",
                                   f"step_00000003.{suffix}"))
    second = run_federation(spec, 5, cfg=_cfg(), ckpt_root=root,
                            resume=True)
    # the replayed round 2 is answered from cache: history gains only
    # the NEW rounds (3, 4 per party), not the replay
    stitched = np.concatenate([history_losses(first),
                               history_losses(second)])
    np.testing.assert_array_equal(stitched, history_losses(cont))
    for m in range(2):
        np.testing.assert_array_equal(
            cont["parties"][m]["final_w"]["w"],
            second["parties"][m]["final_w"]["w"])


@runtime
@slow
def test_resume_rewinds_party_when_server_snapshot_lags(tmp_path):
    """The OTHER hard-kill window: the server's newest snapshot is gone
    (stands in for a kill before the cadence snapshot landed) while the
    parties checkpointed further. On resume the welcome handshake tells
    each party the server's restored progress, the party REWINDS to it,
    and the lost rounds re-execute deterministically — the re-run
    entries and the continuation both match the uninterrupted run."""
    import os

    from repro.checkpoint import available_steps

    spec = _spec()
    cont = run_federation(spec, 5, cfg=_cfg())
    root = str(tmp_path / "ck")
    run_federation(spec, 3, cfg=_cfg(), ckpt_root=root)
    server_dir = os.path.join(root, "server")
    steps = available_steps(server_dir)
    assert len(steps) > 1                # cadence snapshots exist
    for suffix in ("npz", "json"):       # drop the newest server snapshot
        os.remove(os.path.join(server_dir, f"step_{steps[-1]:08d}.{suffix}"))
    restored_updates = available_steps(server_dir)[-1]
    second = run_federation(spec, 5, cfg=_cfg(), ckpt_root=root,
                            resume=True)
    # the resumed run re-executes the lost updates then continues: its
    # history is exactly the uninterrupted run's tail from the restored
    # update count onward
    np.testing.assert_array_equal(history_losses(second),
                                  history_losses(cont)[restored_updates:])
    for m in range(2):
        np.testing.assert_array_equal(
            cont["parties"][m]["final_w"]["w"],
            second["parties"][m]["final_w"]["w"])


@runtime
@slow
def test_arrival_schedule_enforces_tau_staleness_bound():
    """Assumption 4 ENFORCED: with a slow-link straggler the fast party
    would race arbitrarily far ahead under plain arrival dispatch;
    ``max_staleness=1`` parks its rounds until the laggard catches up.
    The server reports both the parking events (proof the bound engaged)
    and the maximum staleness actually admitted (never above tau)."""
    spec, rounds = _spec(), 5
    plan = FailurePlan({1: PartyFault(slow_send_s=0.25)})
    res = run_federation(spec, rounds, plan=plan,
                         cfg=_cfg(schedule="arrival", max_staleness=1))
    srv = res["server"]
    assert srv["parked"] > 0                  # the fast party got parked
    assert srv["staleness_max"] <= 1          # tau held for every round
    assert srv["processed"] == [rounds, rounds]
    assert srv["updates"] == 2 * rounds
    h = history_losses(res)
    assert len(h) == 2 * rounds and np.isfinite(h).all()


@runtime
@slow
def test_arrival_schedule_tolerates_crash_and_straggler():
    """AsyREVEL's async dispatch on the real transport: a crash+rejoin
    and a slow-link straggler; every party still completes its budget
    and the trajectory stays finite."""
    spec, rounds = _spec(), 5
    plan = FailurePlan({0: PartyFault(crash_at_round=2, rejoin_delay_s=0.3),
                        1: PartyFault(slow_send_s=0.05)})
    res = run_federation(spec, rounds, plan=plan,
                         cfg=_cfg(schedule="arrival"))
    assert res["server"]["processed"] == [rounds, rounds]
    assert res["server"]["updates"] == 2 * rounds
    h = history_losses(res)
    assert len(h) == 2 * rounds and np.isfinite(h).all()
