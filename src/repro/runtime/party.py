"""Party worker process: one OS process per party, dialing the server
over TCP and running Algorithm 1's party side round by round.

The round math is EXACTLY core/async_host.py's helpers
(``party_round_prepare`` / ``party_round_messages`` /
``party_round_apply``) — the only difference from the in-process
executors is that the up-link Messages are serialized onto a socket and
the loss_down reply is read back off it. Every message still passes
through the party's local :class:`~repro.core.wire.Channel` stack
(outgoing via ``send``, incoming via ``observe``), so per-kind byte
accounting, NetworkChannel pricing, and RecordingChannel transcripts
work unchanged on the real transport.

Elastic resume: the party checkpoints its block every
``RuntimeConfig.ckpt_every`` rounds through ``repro.checkpoint`` (atomic
npz + metadata). Respawned with ``resume=True`` it restores its newest
checkpoint that is not ahead of the server's restored progress (the
welcome handshake carries that count — after a hard kill of the whole
federation the server may be the one lagging), fast-forwards its private
RNG by replaying the completed rounds' draws, and re-executes from
there — any round the server already processed is answered from the
server's reply cache, so the party reconstructs the exact pre-crash
trajectory (losslessness by determinism + at-least-once delivery + an
idempotent server).
"""
from __future__ import annotations

import os
import time

import jax

from repro.checkpoint import (available_steps, restore_checkpoint,
                              save_checkpoint)
from repro.configs.base import RuntimeConfig
from repro.core.exchange import CommsMeter, ZOExchange
from repro.core.wire import InMemoryChannel
from repro.obs import maybe_tracer, trace
from repro.runtime.failures import CRASH_EXIT_CODE, PartyFault
from repro.runtime.problem import build_problem
from repro.runtime.transport import (ConnectionClosed, FramedSocket,
                                     TransportError, TransportTimeout,
                                     connect_with_retry)


def _recv_reply(fsock: FramedSocket, cfg: RuntimeConfig, peer="server"):
    """Wait for the round's loss_down, pinging every ``heartbeat_s``
    while it is late; answered pongs confirm liveness and do NOT consume
    the wait budget — the hard bound is ``request_timeout_s *
    max_retries`` of total silence-or-waiting, whichever comes first.

    Each ping/pong pair is RTT-timed through the tracer's local FIFO
    (pings and pongs are 1:1 and in-order on this socket) — the control
    frames themselves are untouched, so traced and untraced runs put
    identical bytes on the wire."""
    tr = maybe_tracer()
    deadline = time.monotonic() + cfg.request_timeout_s * cfg.max_retries
    while True:
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            raise TransportTimeout(
                "no loss_down reply within the retry budget")
        try:
            frame_type, obj = fsock.recv(
                timeout=min(cfg.heartbeat_s, remaining))
        except TransportTimeout:
            if tr is not None:
                tr.ping_sent(peer)
            fsock.send_control({"type": "ping"})   # probe; keep waiting
            continue
        if frame_type == "ctl":
            if obj.get("type") == "pong":
                if tr is not None:
                    tr.pong_received(peer)
                continue
            raise TransportError(f"unexpected control frame {obj!r}")
        if obj.kind != "loss_down":
            raise TransportError(f"expected loss_down, got {obj.kind}")
        return obj


def _pick_resume_round(ckpt_dir: str | None, server_processed: int):
    """The round to resume from: the newest own checkpoint that is NOT
    ahead of the server's restored progress. After a hard kill of the
    whole federation the server's snapshot may lag the party's (server
    snapshots on a cadence, parties every ckpt_every rounds) — the
    server cannot replay forward, so the party rewinds and re-executes;
    rounds the server did process are answered from its reply cache."""
    if ckpt_dir is None:
        return None, 0
    usable = [s for s in available_steps(ckpt_dir) if s <= server_processed]
    return (usable[-1], usable[-1]) if usable else (None, 0)


def party_main(spec: dict, m: int, port: int, rounds: int,
               cfg: RuntimeConfig, fault: PartyFault | None = None,
               ckpt_dir: str | None = None, resume: bool = False,
               result_q=None) -> dict:
    """Entry point of one party process (spawn target)."""
    import numpy as np

    from repro.core import async_host

    prob = build_problem(spec)
    model, vfl = prob.model, prob.vfl
    n = len(prob.y)
    _, party_keys, _ = async_host.trainer_keys(prob.seed, model.num_parties)
    w_m = model.init_party(party_keys[m], m)
    ex = ZOExchange.from_config(vfl, meter=CommsMeter())
    channel = InMemoryChannel()
    rng = np.random.default_rng(async_host.party_rng_seed(prob.seed, m))

    fsock = connect_with_retry(cfg.host, port, cfg.connect_retries,
                               cfg.connect_backoff_s)
    try:
        fsock.send_control({"type": "hello", "party": m, "resume": resume})
        frame_type, welcome = fsock.recv(timeout=cfg.request_timeout_s)
        if frame_type != "ctl" or welcome.get("type") != "welcome":
            raise TransportError(f"bad handshake reply: {welcome!r}")

        start_round = 0
        if resume and ckpt_dir is not None:
            step, start_round = _pick_resume_round(
                ckpt_dir, int(welcome.get("processed", 0)))
            if step is not None:
                w_m, _ = restore_checkpoint(ckpt_dir, w_m, step)
                # fast-forward the private stream past the completed
                # rounds — same two draws per round as draw_round
                for _ in range(start_round):
                    async_host.draw_round(rng, n, prob.batch_size)

        for rnd in range(start_round, rounds):
            if (fault is not None and fault.crash_at_round == rnd
                    and not resume):
                # scripted abrupt death: no goodbye, no checkpoint flush
                os._exit(CRASH_EXIT_CODE)
            with trace("party_round", party=int(m), round=int(rnd)):
                idx, key = async_host.draw_round(rng, n, prob.batch_size)
                prep = async_host.party_round_prepare(model, vfl, ex, w_m,
                                                      prob.X, idx, key, m)
                if cfg.compute_cost_s > 0:
                    time.sleep(cfg.compute_cost_s)
                if fault is not None and fault.slow_send_s > 0:
                    # straggler link: span the injected stall so a merged
                    # trace shows WHERE the slow party's round went (the
                    # live straggler detector needs only party_round, but
                    # an operator reading the Perfetto view needs this)
                    with trace("party_stall", party=int(m),
                               round=int(rnd)):
                        time.sleep(fault.slow_send_s)
                msg_c, msg_hats = async_host.party_round_messages(
                    channel, m, rnd, idx, prep)
                fsock.send_message(msg_c)
                for msg in msg_hats:
                    fsock.send_message(msg)
                with trace("party_wait_reply", party=int(m),
                           round=int(rnd)):
                    raw = _recv_reply(fsock, cfg)
                reply = channel.observe(raw)
                with trace("party_apply", party=int(m), round=int(rnd)):
                    w_m = async_host.party_round_apply(vfl, ex, w_m, prep,
                                                       reply.scalars())
                if ckpt_dir is not None and (rnd + 1) % cfg.ckpt_every == 0:
                    save_checkpoint(ckpt_dir, rnd + 1, w_m,
                                    {"party": m, "round": rnd + 1})

        if ckpt_dir is not None and rounds % cfg.ckpt_every != 0:
            save_checkpoint(ckpt_dir, rounds, w_m,
                            {"party": m, "round": rounds})
        fsock.send_control({"type": "bye", "party": m})
        aborted = False
    except ConnectionClosed:
        # server went away mid-run: leave the checkpoint as the record
        # and report what we have, FLAGGED (the harness decides whether
        # the server's own report explains the abort)
        aborted = True
    finally:
        fsock.close()

    result = {
        "party": m,
        "platform": jax.default_backend(),
        "aborted": aborted,
        "rounds": rounds,
        "bytes_by_kind": dict(channel.bytes_by_kind),
        "msgs_by_kind": dict(channel.msgs_by_kind),
        "up_bytes": ex.meter.up_bytes,
        "socket_bytes_out": fsock.bytes_out,
        "socket_bytes_in": fsock.bytes_in,
        "final_w": {k: np.asarray(v) for k, v in w_m.items()},
    }
    tr = maybe_tracer()
    if tr is not None:
        # the harness may SIGTERM this process right after reading the
        # result (skipping atexit) — get the trace tail to disk first
        tr.flush()
    if result_q is not None:
        result_q.put(("party", result))
    return result
