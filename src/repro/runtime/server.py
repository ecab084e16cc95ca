"""The AsyREVEL server as a standalone OS process.

Topology: one listener socket; each party dials in, handshakes
(hello/welcome), and gets a receiver thread that assembles its frames
into COMPLETE rounds (one c_up + num_directions c_hat_up with the same
round index) and queues them for the dispatcher. The dispatcher — the
process's main thread — pops rounds in the configured schedule order and
drives the SAME ``core/async_host._Server.handle`` the in-process
executors use, so server math, perturbation streams, and byte
accounting are shared with the simulated paths by construction:

  schedule='serial'   strict round-robin over parties: party m's round g
                      is processed only after every party's round g-1 and
                      parties 0..m-1's round g. This is the reference
                      order — bit-identical to HostAsyncTrainer.run_serial.
  schedule='arrival'  complete rounds are processed in socket-arrival
                      order (AsyREVEL: nobody waits for a straggler),
                      optionally bounded by ``cfg.max_staleness`` — the
                      paper's tau (Assumption 4) ENFORCED: rounds racing
                      more than tau ahead of the slowest party park
                      until it catches up.

Fault tolerance: a disconnect (EOF without a goodbye) triggers a
membership-change checkpoint of the server state (w0 + c_table + update
count) through ``repro.checkpoint``; the dispatcher keeps waiting and a
rejoining party re-attaches to its slot. Delivery is at-least-once with
an idempotent server: every processed round's reply is cached per
(party, round), and a replayed round — a rejoined party re-executing
from its checkpoint — is answered from the cache WITHOUT advancing any
server state. Stale-link queue entries are dropped wholesale: any round
the server never processed will be resent by the rejoined party, and any
round it did process is in the cache.

Heartbeats ride the receiver threads (ping -> pong immediately, even
while the dispatcher is busy), and every blocking operation carries a
timeout bounded by the run deadline — a hung party fails the federation
loudly instead of wedging it.
"""
from __future__ import annotations

import os
import queue
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import (latest_step, load_metadata, restore_checkpoint,
                              save_checkpoint)
from repro.configs import NETWORK_PROFILES
from repro.configs.base import RuntimeConfig
from repro.core.exchange import CommsMeter, ZOExchange
from repro.core.wire import (InMemoryChannel, NetworkChannel,
                             RecordingChannel)
from repro.obs import maybe_tracer, trace
from repro.runtime.problem import build_problem
from repro.runtime.transport import (ConnectionClosed, FramedSocket,
                                     TransportError, TransportTimeout)


class FederationError(RuntimeError):
    pass


def make_channel(kind: str):
    """Channel factory by name — the observation stack of one endpoint
    ('recording:<inner>' wraps, 'network:<profile>' prices)."""
    if kind.startswith("recording"):
        _, _, inner = kind.partition(":")
        return RecordingChannel(make_channel(inner) if inner else None)
    if kind.startswith("network"):
        _, _, profile = kind.partition(":")
        return NetworkChannel(NETWORK_PROFILES[profile or "lan"])
    if kind in ("inmemory", ""):
        return InMemoryChannel()
    raise ValueError(f"unknown channel kind {kind!r}")


class _PartyLink:
    """The server's view of one party connection (replaced on rejoin)."""

    def __init__(self, fsock: FramedSocket, seq: int):
        self.fsock = fsock
        self.seq = seq


class RuntimeServer:
    def __init__(self, spec: dict, rounds: int, cfg: RuntimeConfig,
                 channel_kind: str = "inmemory",
                 ckpt_dir: str | None = None, resume: bool = False):
        from repro.core import async_host

        self.spec = spec
        self.rounds = rounds
        self.cfg = cfg
        self.ckpt_dir = ckpt_dir
        prob = build_problem(spec)
        self.q = prob.model.num_parties
        self.K = prob.vfl.num_directions
        self.channel = make_channel(channel_kind)
        self.ex = ZOExchange.from_config(prob.vfl, meter=CommsMeter())
        server_key, _, pert_key = async_host.trainer_keys(prob.seed, self.q)
        self.core = async_host._Server(prob.model, prob.vfl, len(prob.y),
                                       server_key, self.ex,
                                       pert_key=pert_key,
                                       channel=self.channel)
        self.core.y = jnp.asarray(prob.y)
        self._deadline = time.monotonic() + cfg.deadline_s
        self._links: dict[int, _PartyLink] = {}  # guarded-by: self._links_lock
        self._links_lock = threading.Lock()
        self._inbox: dict[int, queue.Queue] = {
            m: queue.Queue() for m in range(self.q)}
        self._global_inbox: queue.Queue = queue.Queue()
        self._processed = [0] * self.q      # guarded-by: self.core.lock
        # per (party, round): (reply Message, link seq it went out on,
        # whether that send succeeded) — the at-least-once dedup cache
        self._replies: dict[int, dict[int, tuple]] = {  # guarded-by: self.core.lock
            m: {} for m in range(self.q)}
        self._errors: list[BaseException] = []
        self._bye = [False] * self.q
        self._disconnects = 0
        # Assumption-4 enforcement bookkeeping (arrival schedule):
        # rounds parked for racing > max_staleness ahead, and the max
        # staleness actually admitted to processing
        self._parked_events = 0
        self._staleness_max = 0
        self._dead_bytes_in = 0
        self._dead_bytes_out = 0
        self._listener: FramedSocket | None = None
        if resume and ckpt_dir is not None:
            self._restore()

    # -- membership / elastic resume ---------------------------------------
    def _snapshot(self, reason: str) -> None:
        """Checkpoint the full server state through repro.checkpoint —
        called on every membership change and at run end. Besides model
        state the metadata records per-party progress and each party's
        LAST reply: a party killed between the server processing its
        round and the party checkpointing the result will replay that
        round after a whole-federation restart, and it must be answered
        from the persisted cache (the live server state has already
        advanced past it)."""
        if self.ckpt_dir is None:
            return
        # snapshot runs on receiver threads (disconnects) AND the
        # dispatcher (cadence/run-end) while handle() mutates core state
        # and _process grows the reply cache — read everything under the
        # core lock so (updates, w0, c_table, cache) is one consistent
        # cut, then write outside it
        with self.core.lock:
            step = self.core.losses.updates
            w0 = self.core.w0
            c_table = np.array(self.core.c_table, np.float32)
            processed = list(self._processed)
            # the FULL cache, not just each party's last reply: a
            # resumed party replays every round since its last
            # checkpoint. Entries are (1+K) scalars per round.
            replies = {
                str(m): [{"rnd": rnd, "round": reply.round,
                          "scalars": list(reply.scalars())}
                         for rnd, (reply, _, _) in sorted(cache.items())]
                for m, cache in self._replies.items() if cache}
        save_checkpoint(self.ckpt_dir, step,
                        {"w0": w0, "c_table": jnp.asarray(c_table)},
                        {"updates": step, "reason": reason,
                         "processed": processed, "replies": replies})

    def _restore(self) -> None:
        from repro.core.wire import SERVER as _SERVER
        from repro.core.wire import Message, party as _party

        step = latest_step(self.ckpt_dir)
        if step is None:
            return
        # restore runs from __init__ before anything listens, but the
        # guarded state is still only ever written under its lock — one
        # discipline, no "safe because init" special case to reason about
        with self.core.lock:
            state = {"w0": self.core.w0,
                     "c_table": jnp.asarray(self.core.c_table)}
            state, _ = restore_checkpoint(self.ckpt_dir, state, step)
            self.core.w0 = state["w0"]
            # a fresh WRITABLE copy — np.asarray over a jax buffer is a
            # read-only view, and handle() assigns into the c table
            self.core.c_table = np.array(state["c_table"], np.float32)
            meta = load_metadata(self.ckpt_dir, step) or {}
            self.core.losses.updates = int(meta.get("updates", step))
            self._processed = [int(x) for x in
                               meta.get("processed", [0] * self.q)]
            for m_str, recs in (meta.get("replies") or {}).items():
                m = int(m_str)
                for rec in recs:
                    reply = Message.make(
                        "loss_down", _SERVER, _party(m), int(rec["round"]),
                        tuple(float(s) for s in rec["scalars"]))
                    self._replies[m][int(rec["rnd"])] = (reply, -1, False)

    def _on_disconnect(self, m: int) -> None:
        self._disconnects += 1
        tr = maybe_tracer()
        if tr is not None:
            # a live monitor (and the merged trace) sees WHO dropped —
            # joined against the flight recorder's last rounds by party
            tr.counter("party_disconnect", party=int(m))
        self._snapshot(f"party {m} disconnected")

    # -- connection handling -----------------------------------------------
    def _accept_loop(self, server_sock) -> None:
        while True:
            try:
                conn, _ = server_sock.accept()
            except OSError:
                return                      # listener closed: shutting down
            threading.Thread(target=self._handshake,
                             args=(FramedSocket(conn),), daemon=True).start()

    def _handshake(self, fsock: FramedSocket) -> None:
        try:
            frame_type, hello = fsock.recv(timeout=self.cfg.request_timeout_s)
            if frame_type != "ctl" or hello.get("type") != "hello":
                raise TransportError(f"expected hello, got {hello!r}")
            m = int(hello["party"])
            if not 0 <= m < self.q:
                raise TransportError(f"unknown party index {m}")
            with self._links_lock:
                prev = self._links.get(m)
                seq = prev.seq + 1 if prev else 0
                if prev is not None:
                    # keep the dead link's measured socket traffic in the
                    # run totals before the rejoin replaces it
                    self._dead_bytes_in += prev.fsock.bytes_in
                    self._dead_bytes_out += prev.fsock.bytes_out
                self._links[m] = _PartyLink(fsock, seq)
            # one consistent (updates, processed) cut: the dispatcher
            # advances both inside _process's critical section, and a
            # welcome straddling that advance would tell a resuming party
            # to rewind to a round the server has already answered
            with self.core.lock:
                welcome = {"type": "welcome", "party": m,
                           "updates": self.core.losses.updates,
                           # how far THIS party's rounds have been
                           # processed: a resuming party whose own
                           # checkpoint is ahead of a restored
                           # server must rewind to this
                           "processed": self._processed[m]}
            fsock.send_control(welcome)
            self._receive_loop(m, fsock, seq)
        except (TransportError, OSError) as e:
            self._errors.append(e)
            fsock.close()

    def _receive_loop(self, m: int, fsock: FramedSocket, seq: int) -> None:
        """Assemble complete rounds for party m; reply to pings inline."""
        pending: dict[int, dict] = {}
        while True:
            try:
                frame_type, obj = fsock.recv(timeout=self.cfg.deadline_s)
            except (ConnectionClosed, TransportTimeout, TransportError):
                self._on_disconnect(m)
                return
            if frame_type == "ctl":
                t = obj.get("type")
                if t == "ping":
                    fsock.send_control({"type": "pong"})
                elif t == "bye":
                    self._bye[m] = True
                    return
                continue
            msg = obj
            slot = pending.setdefault(msg.round, {"c": None, "hats": []})
            if msg.kind == "c_up":
                slot["c"] = msg
            elif msg.kind == "c_hat_up":
                slot["hats"].append(msg)
            else:
                self._errors.append(TransportError(
                    f"party {m} sent unexpected {msg.kind}"))
                return
            if slot["c"] is not None and len(slot["hats"]) == self.K:
                del pending[msg.round]
                item = (seq, msg.round, slot["c"], tuple(slot["hats"]))
                self._inbox[m].put(item)
                self._global_inbox.put((m,) + item)

    # -- dispatch ----------------------------------------------------------
    # zvlint: disable=lock-discipline — failure-path read of _processed
    # for the exception message only
    def _check(self) -> None:
        if time.monotonic() > self._deadline:
            raise FederationError(
                f"federation deadline exceeded; processed={self._processed} "
                f"of {self.rounds} rounds x {self.q} parties "
                f"({self._disconnects} disconnects)")

    def _current_link(self, m: int) -> _PartyLink | None:
        with self._links_lock:
            return self._links.get(m)

    def _resend_cached(self, m: int, rnd: int) -> None:
        """A replayed round from a rejoined party: answer from the cache
        without touching server state — unless the reply already went out
        on the party's CURRENT link (then a resend would double-deliver)."""
        # the dispatcher calls this, but _process (same thread) grows and
        # PRUNES the cache under the core lock while snapshot readers
        # iterate it — reads take the lock too so the membership test and
        # the lookup see one cache state
        with self.core.lock:
            if rnd not in self._replies[m]:
                raise FederationError(
                    f"party {m} replayed round {rnd} but its reply is not "
                    f"in the cache (processed={self._processed[m]}) — the "
                    "server state has advanced past it and cannot answer "
                    "losslessly")
            reply, sent_seq, sent_ok = self._replies[m][rnd]
        tr = maybe_tracer()
        if tr is not None:
            tr.counter("reply_cache_hit", party=int(m), round=int(rnd))
        link = self._current_link(m)
        if link is None or (sent_ok and sent_seq == link.seq):
            return
        try:
            link.fsock.send_message(reply)    # send outside the lock
            with self.core.lock:
                self._replies[m][rnd] = (reply, link.seq, True)
        except (TransportError, OSError):
            pass                             # it will be replayed again

    def _process(self, m: int, msg_c, msg_hats) -> None:
        # span covers admission-to-reply: observe + handle + send + cache
        with trace("server_process", party=int(m), round=int(msg_c.round)):
            self._process_round(m, msg_c, msg_hats)

    def _process_round(self, m: int, msg_c, msg_hats) -> None:
        # observe the up-link through the server's channel stack at
        # processing time: transcript/counter order equals the schedule
        # order, and replayed duplicates are never double-counted
        msg_c = self.channel.observe(msg_c)
        msg_hats = tuple(self.channel.observe(h) for h in msg_hats)
        # handle's state advance and the reply/progress bookkeeping are
        # ONE critical section (the core lock is reentrant): a
        # disconnect-time _snapshot on a receiver thread can never
        # persist updates/w0 advanced past processed/the reply cache —
        # that torn cut would double-apply a round on resume
        with self.core.lock:
            rnd = self._processed[m]
            down = self.core.handle(msg_c, msg_hats)  # accounts loss_down
            link = self._current_link(m)
            self._replies[m][rnd] = (down, link.seq if link else -1,
                                     False)
            self._processed[m] = rnd + 1
            # prune replays that can no longer be requested: a resuming
            # party rewinds at most to its previous checkpoint, which is
            # within ckpt_every rounds of the processed count — the
            # cache (and every snapshot of it) stays O(ckpt_every)
            cutoff = self._processed[m] - self.cfg.ckpt_every - 1
            for old in [r for r in self._replies[m] if r < cutoff]:
                del self._replies[m][old]
        if link is not None:
            try:
                link.fsock.send_message(down)
                with self.core.lock:
                    self._replies[m][rnd] = (down, link.seq, True)
            except (TransportError, OSError):
                pass        # party died mid-round; cache serves the rejoin
        # cadence snapshot: bounds what a hard kill of the WHOLE
        # federation (no disconnect event ever fires) can lose; a
        # resuming party ahead of the restored server rewinds to the
        # server's processed count (see party._pick_resume_round)
        if self.ckpt_dir is not None:
            with self.core.lock:
                done = sum(self._processed)
            if done % (self.q * self.cfg.ckpt_every) == 0:
                self._snapshot("cadence")

    def _pop(self, inbox: queue.Queue):
        while True:
            self._check()
            if self._errors:
                raise FederationError(f"protocol error: {self._errors[0]}")
            try:
                return inbox.get(timeout=0.5)
            except queue.Empty:
                continue

    # zvlint: disable=lock-discipline — the dispatcher thread is the SOLE
    # writer of _processed, so its own unlocked reads cannot tear; every
    # cross-thread reader (_snapshot, _handshake) takes the core lock
    def _dispatch_serial(self) -> None:
        for g in range(self.rounds):
            for m in range(self.q):
                if g < self._processed[m]:
                    continue                 # restored progress (resume)
                while True:
                    seq, rnd, msg_c, hats = self._pop(self._inbox[m])
                    link = self._current_link(m)
                    if link is not None and seq < link.seq:
                        continue             # stale pre-crash link: resent
                    if rnd < self._processed[m]:
                        self._resend_cached(m, rnd)
                        continue
                    if rnd > self._processed[m]:
                        raise FederationError(
                            f"party {m} skipped ahead: sent round {rnd}, "
                            f"expected {self._processed[m]}")
                    break
                self._process(m, msg_c, hats)

    # zvlint: disable=lock-discipline — dispatcher-only reads of
    # _processed (see _dispatch_serial); mutation happens in _process
    # under the core lock
    def _dispatch_arrival(self) -> None:
        """Arrival order, bounded by the paper's tau (Assumption 4) when
        ``cfg.max_staleness`` is set: a round that would race more than
        tau rounds ahead of the SLOWEST party is parked and re-admitted
        once the laggard catches up. The slowest party's own round has
        staleness 0, so it is always admissible — parking can stall the
        fast parties but never the whole dispatcher (a laggard that
        never delivers is a deadline failure, as before)."""
        total = self.rounds * self.q
        tau = self.cfg.max_staleness
        parked: dict[int, tuple] = {}          # party -> (seq, rnd, c, hats)
        park_t0: dict[int, float] = {}         # party -> parking start
        tr = maybe_tracer()

        def staleness(rnd: int) -> int:
            return rnd - min(self._processed)

        while sum(self._processed) < total:
            item = None
            # oldest parked round first: FIFO among the admissible ones
            for pm in sorted(parked, key=lambda p: parked[p][1]):
                if staleness(parked[pm][1]) <= tau:
                    item = (pm,) + parked.pop(pm)
                    if tr is not None:
                        tr.histo("parked_s",
                                 time.monotonic() - park_t0.pop(pm),
                                 party=int(pm), round=int(item[2]))
                    break
            if item is None:
                item = self._pop(self._global_inbox)
            m, seq, rnd, msg_c, hats = item
            link = self._current_link(m)
            if link is not None and seq < link.seq:
                continue             # stale pre-crash link: will be resent
            if rnd < self._processed[m]:
                self._resend_cached(m, rnd)
                continue
            if rnd > self._processed[m]:
                raise FederationError(
                    f"party {m} skipped ahead: sent round {rnd}, "
                    f"expected {self._processed[m]}")
            if tau is not None and staleness(rnd) > tau:
                parked[m] = (seq, rnd, msg_c, hats)
                park_t0[m] = time.monotonic()
                self._parked_events += 1
                continue
            self._staleness_max = max(self._staleness_max, staleness(rnd))
            if tr is not None:
                tr.histo("staleness", staleness(rnd),
                         party=int(m), round=int(rnd))
            self._process(m, msg_c, hats)

    # -- run ---------------------------------------------------------------
    def serve(self, port_cb=None) -> dict:
        import socket

        server_sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        server_sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        server_sock.bind((self.cfg.host, self.cfg.port))
        server_sock.listen(self.q + 4)
        port = server_sock.getsockname()[1]
        if port_cb is not None:
            port_cb(port)
        accept_thread = threading.Thread(target=self._accept_loop,
                                         args=(server_sock,), daemon=True)
        accept_thread.start()
        try:
            if self.cfg.schedule == "serial":
                self._dispatch_serial()
            elif self.cfg.schedule == "arrival":
                self._dispatch_arrival()
            else:
                raise ValueError(
                    f"unknown schedule {self.cfg.schedule!r}; "
                    f"have serial, arrival")
            # wait for every party's goodbye (bounded): the last-served
            # party still has to apply + checkpoint before its bye, and
            # closing early would miscount it as a disconnect. Scale
            # with the configured patience, not a magic constant.
            wait_until = time.monotonic() + min(
                self.cfg.deadline_s,
                max(10.0, 2.0 * self.cfg.request_timeout_s))
            while not all(self._bye) and time.monotonic() < wait_until:
                time.sleep(0.02)
            self._snapshot("run complete")
        finally:
            server_sock.close()
            with self._links_lock:
                links = list(self._links.values())
            for link in links:
                link.fsock.close()

        # the dispatcher has returned, but receiver threads for unclean
        # parties may still be alive — take one last consistent cut
        with self.core.lock:
            res = self.core.losses
            processed = list(self._processed)
            w0 = {k: np.asarray(v) for k, v in self.core.w0.items()}
        bytes_by_kind = dict(self.channel.bytes_by_kind)
        transcript = getattr(self.channel, "transcript", None)
        return {
            "platform": jax.default_backend(),
            "updates": res.updates,
            "history": [(float(t), float(h)) for t, h in res.history],
            "bytes_by_kind": bytes_by_kind,
            "msgs_by_kind": dict(self.channel.msgs_by_kind),
            "transcript_bytes_by_kind": (
                dict(transcript.bytes_by_kind()) if transcript is not None
                else None),
            "transcript_len": (len(transcript) if transcript is not None
                               else None),
            "disconnects": self._disconnects,
            "parked": self._parked_events,
            "staleness_max": self._staleness_max,
            "processed": processed,
            "w0": w0,
            "socket_bytes_in": self._dead_bytes_in + sum(
                link.fsock.bytes_in for link in links),
            "socket_bytes_out": self._dead_bytes_out + sum(
                link.fsock.bytes_out for link in links),
        }


def server_main(spec: dict, rounds: int, cfg: RuntimeConfig,
                channel_kind: str, ckpt_dir: str | None, resume: bool,
                port_q, result_q) -> None:
    """Entry point of the server process (spawn target)."""
    try:
        server = RuntimeServer(spec, rounds, cfg, channel_kind=channel_kind,
                               ckpt_dir=ckpt_dir, resume=resume)
        result = server.serve(port_cb=port_q.put)
        tr = maybe_tracer()
        if tr is not None:
            # the harness may SIGTERM us right after reading the result
            # (skipping atexit) — get the trace tail to disk first
            tr.flush()
        result_q.put(("server", result))
    except BaseException as e:  # noqa: BLE001 — report, then die loudly
        import traceback
        result_q.put(("server_error",
                      "".join(traceback.format_exception(e)).strip()))
        # flush the queue's feeder thread BEFORE dying, or the error
        # report itself is lost and the harness only sees a deadline
        result_q.close()
        result_q.join_thread()
        os._exit(1)
