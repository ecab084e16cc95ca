"""Federated serving over the PR-4 TCP runtime: real party processes
answering inference queries.

Topology is the training harness's, inverted at the server: the PARENT
process is the serving front end — it binds the listener, handshakes each
dialing party (hello/welcome; the hello carries the params version the
party restored from its checkpoint), and drives a
:class:`~repro.serving.federated.FederatedServingEngine` whose backends
write ``serve_down`` frames to the party sockets and read batched
``c_up`` answers back. Issuing every party's frame before collecting any
answer makes the remote parties compute genuinely concurrently — the
same async-overlap contract the in-process backend simulates.

The party process (``serving_party_main``) reuses the training worker's
discipline wholesale: ``connect_with_retry`` dial-in, hello/welcome,
ping->pong heartbeats answered inline while it waits, a per-round
idempotent reply cache (a re-delivered query round is answered from the
cache without recomputing), and blocks restored from ``repro.checkpoint``
when a checkpoint directory is given — serving answers come from the
trained block, not a fresh init. Compute goes through the SAME jitted
single-sample forward as the in-process backend
(``serving.federated.answer_serve_query``), so a TCP serving round is
bitwise identical to the in-memory engine's — tests pin it.
"""
from __future__ import annotations

import multiprocessing as mp
import os
import queue as queue_mod
import socket
import time

import jax
import numpy as np

from repro.configs.base import RuntimeConfig
from repro.core.exchange import ZOExchange
from repro.core.wire import InMemoryChannel, Message
from repro.obs import MONITOR_ENV, maybe_tracer, trace
# serving.py is the serving parent's monitor entry point (same exception
# the training harness carries in analysis/rules_obs.py). Serving c_up
# payloads legitimately vary with slot occupancy, so its engine runs
# with the byte-drift detector off.
from repro.obs.health import HealthEngine
from repro.obs.monitor import MonitorServer
from repro.runtime.harness import (CHILD_PLATFORM, _ensure_child_pythonpath,
                                   _terminate, export_env, restore_env)
from repro.runtime.problem import build_problem
from repro.runtime.server import FederationError, make_channel
from repro.runtime.transport import (ConnectionClosed, FramedSocket,
                                     TransportError, TransportTimeout,
                                     byte_counts, connect_with_retry)
from repro.serving.federated import (FederatedServingEngine, ServeRequest,
                                     answer_serve_query)


# ----------------------------------------------------------- party side --

def serving_party_main(spec: dict, m: int, port: int, cfg: RuntimeConfig,
                       ckpt_dir: str | None = None, result_q=None) -> dict:
    """Entry point of one serving party process (spawn target): restore
    the block, dial in, answer serve_down queries until 'done'."""
    from repro.checkpoint import latest_step, restore_checkpoint
    from repro.core import async_host

    prob = build_problem(spec)
    model = prob.model
    _, party_keys, _ = async_host.trainer_keys(prob.seed, model.num_parties)
    w_m = model.init_party(party_keys[m], m)
    version = 0
    if ckpt_dir is not None:
        step = latest_step(ckpt_dir)
        if step is not None:
            w_m, _ = restore_checkpoint(ckpt_dir, w_m, step)
            version = int(step)
    ex = ZOExchange.from_config(prob.vfl)
    channel = InMemoryChannel()
    replies: dict[int, Message] = {}      # round -> cached c_up (idempotent)
    served = 0

    fsock = connect_with_retry(cfg.host, port, cfg.connect_retries,
                               cfg.connect_backoff_s)
    try:
        fsock.send_control({"type": "hello", "party": m, "serve": True,
                            "version": version})
        frame_type, welcome = fsock.recv(timeout=cfg.request_timeout_s)
        if frame_type != "ctl" or welcome.get("type") != "welcome":
            raise TransportError(f"bad handshake reply: {welcome!r}")
        while True:
            try:
                frame_type, obj = fsock.recv(timeout=cfg.deadline_s)
            except TransportTimeout:
                break
            if frame_type == "ctl":
                t = obj.get("type")
                if t == "ping":
                    fsock.send_control({"type": "pong"})
                    continue
                if t == "done":
                    break
                raise TransportError(f"unexpected control frame {obj!r}")
            if obj.kind != "serve_down":
                raise TransportError(f"expected serve_down, got {obj.kind}")
            msg = channel.observe(obj)
            if msg.round in replies:          # re-delivered query round:
                reply = replies[msg.round]    # answer from the cache
            else:
                with trace("serve_answer", party=int(m),
                           round=int(msg.round)):
                    reply = channel.send(answer_serve_query(
                        model, m, w_m, prob.X, ex, msg, version=version))
                replies[msg.round] = reply
                served += len(np.asarray(msg.payload).reshape(-1))
            fsock.send_message(reply)
        fsock.send_control({"type": "bye", "party": m})
        aborted = False
    except ConnectionClosed:
        aborted = True
    finally:
        fsock.close()

    result = {
        "party": m,
        "platform": jax.default_backend(),
        "aborted": aborted,
        "served": served,
        "version": version,
        "bytes_by_kind": dict(channel.bytes_by_kind),
        "msgs_by_kind": dict(channel.msgs_by_kind),
        **byte_counts([fsock]),
    }
    tr = maybe_tracer()
    if tr is not None:
        tr.flush()     # before the result triggers parent-side terminate
    if result_q is not None:
        result_q.put(("party", result))
    return result


# ---------------------------------------------------------- server side --

class RemotePartyBackend:
    """Engine backend over one party's framed socket. ``request`` writes
    the serve_down frame immediately (all parties' frames go out before
    any ``collect`` blocks — the overlap), and ``collect`` waits for the
    batched c_up with the training party's heartbeat discipline: ping
    every ``heartbeat_s`` of silence, answered pongs confirm liveness
    without consuming the ``request_timeout_s * max_retries`` budget."""

    def __init__(self, m: int, fsock: FramedSocket, cfg: RuntimeConfig,
                 version: int = 0):
        self.m = m
        self.fsock = fsock
        self.cfg = cfg
        self.version = int(version)

    def set_params(self, w_m, version: int) -> None:
        raise NotImplementedError(
            "remote blocks rotate by restarting the party on a new "
            "checkpoint, not by pushing params over the serve link")

    def request(self, msg: Message) -> None:
        self.fsock.send_message(msg)

    def collect(self) -> Message:
        cfg = self.cfg
        deadline = time.monotonic() + cfg.request_timeout_s * cfg.max_retries
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise TransportTimeout(
                    f"party {self.m}: no c_up answer within the retry "
                    f"budget")
            try:
                frame_type, obj = self.fsock.recv(
                    timeout=min(cfg.heartbeat_s, remaining))
            except TransportTimeout:
                tr = maybe_tracer()
                if tr is not None:
                    tr.ping_sent(self.m)
                self.fsock.send_control({"type": "ping"})
                continue
            if frame_type == "ctl":
                if obj.get("type") == "pong":
                    tr = maybe_tracer()
                    if tr is not None:
                        tr.pong_received(self.m)
                    continue
                raise TransportError(f"unexpected control frame {obj!r}")
            if obj.kind != "c_up":
                raise TransportError(f"expected c_up, got {obj.kind}")
            return obj

    def close(self) -> None:
        try:
            self.fsock.send_control({"type": "done"})
        except (TransportError, OSError):
            pass
        self.fsock.close()


def _accept_parties(server_sock, q: int,
                    cfg: RuntimeConfig) -> dict[int, tuple]:
    """Accept and handshake exactly q serving parties; returns
    {m: (FramedSocket, version)}."""
    links: dict[int, tuple] = {}
    server_sock.settimeout(cfg.deadline_s)
    while len(links) < q:
        try:
            conn, _ = server_sock.accept()
        except socket.timeout as e:
            raise FederationError(
                f"only {len(links)}/{q} serving parties dialed in") from e
        fsock = FramedSocket(conn)
        frame_type, hello = fsock.recv(timeout=cfg.request_timeout_s)
        if frame_type != "ctl" or hello.get("type") != "hello":
            raise TransportError(f"expected hello, got {hello!r}")
        m = int(hello["party"])
        if not 0 <= m < q or m in links:
            raise TransportError(f"bad party index {m} in serve handshake")
        fsock.send_control({"type": "welcome", "party": m})
        links[m] = (fsock, int(hello.get("version", 0)))
    return links


def run_tcp_serving(spec: dict, sample_ids, *,
                    cfg: RuntimeConfig | None = None, slots: int = 8,
                    cache_entries: int = 2048,
                    ckpt_root: str | None = None,
                    channel_kind: str = "inmemory") -> dict:
    """Serve predictions for ``sample_ids`` with real party processes.

    Returns {'predictions': [(sample_id, prediction), ...] in submit
    order, 'metrics': engine metrics, 'analytic': validated per-kind wire
    bytes, 'parties': per-party reports, 'platform': where this front end
    ran}. When ``ckpt_root`` is given,
    party m restores its newest block from ``<ckpt_root>/party<m>`` (the
    training harness's layout) and its checkpoint step becomes the
    serving params version.
    """
    cfg = cfg or RuntimeConfig()
    prob = build_problem(spec)
    model = prob.model
    q = model.num_parties
    ex = ZOExchange.from_config(prob.vfl)   # engine raises early on DP
    from repro.core import async_host
    server_key, _, _ = async_host.trainer_keys(prob.seed, q)
    w0 = model.init_server(server_key)

    _ensure_child_pythonpath()
    monitor = None
    if cfg.monitor:
        if not cfg.trace_dir:
            raise ValueError("RuntimeConfig.monitor requires trace_dir "
                             "(the collector writes alerts/health there)")
        monitor = MonitorServer(cfg.trace_dir,
                                engine=HealthEngine(byte_drift=False))
    # the training harness's child env: parties run on the CPU (this
    # parent keeps the device for the server engine) and lazily open their
    # own trace files when capture is on
    prev_env = export_env({
        "JAX_PLATFORMS": CHILD_PLATFORM,
        "REPRO_TRACE_DIR": cfg.trace_dir or None,
        MONITOR_ENV: monitor.addr if monitor is not None else None})
    ctx = mp.get_context("spawn")
    result_q = ctx.Queue()

    server_sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    server_sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    server_sock.bind((cfg.host, cfg.port))
    server_sock.listen(q + 4)
    port = server_sock.getsockname()[1]

    def party_ckpt(m: int) -> str | None:
        return (os.path.join(ckpt_root, f"party{m}")
                if ckpt_root is not None else None)

    procs = [ctx.Process(target=serving_party_main,
                         args=(spec, m, port, cfg, party_ckpt(m), result_q),
                         name=f"serve-party{m}", daemon=True)
             for m in range(q)]
    engine = None
    try:
        for p in procs:
            p.start()
        links = _accept_parties(server_sock, q, cfg)
        backends = [RemotePartyBackend(m, links[m][0], cfg,
                                       version=links[m][1])
                    for m in range(q)]
        engine = FederatedServingEngine(
            model, w0, backends, ex, channel=make_channel(channel_kind),
            slots=slots, cache_entries=cache_entries)
        for i, sid in enumerate(np.asarray(sample_ids).reshape(-1)):
            engine.submit(ServeRequest(rid=i, sample_id=int(sid)))
        completed = engine.run()
        analytic = engine.validate_wire()
        engine.close()                      # sends 'done' to every party

        parties: dict = {}
        deadline = time.monotonic() + cfg.deadline_s
        while len(parties) < q:
            if time.monotonic() > deadline:
                raise FederationError(
                    f"got {len(parties)}/{q} serving party reports")
            try:
                tag, payload = result_q.get(timeout=0.5)
            except queue_mod.Empty:
                continue
            if tag == "party":
                parties[payload["party"]] = payload
        for p in procs:
            p.join(timeout=10.0)
        by_rid = sorted(completed, key=lambda r: r.rid)
        out = {
            "platform": jax.default_backend(),
            "predictions": [(r.sample_id, r.prediction) for r in by_rid],
            "metrics": engine.metrics(),
            "analytic": analytic,
            "parties": parties,
        }
        if monitor is not None:
            out["monitor"] = monitor.stop()
        return out
    finally:
        restore_env(prev_env)
        server_sock.close()
        if engine is not None:
            engine.close()
        _terminate(procs)
        if monitor is not None:
            monitor.stop()                 # idempotent: error paths too
