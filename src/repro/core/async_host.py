"""Host-level REAL asynchronous executor — the paper's MPI setup, in threads.

One thread per party + the server state behind a lock; parties loop
independently: sample a minibatch of their PRIVATE feature slice, compute
(c, c_hat), send to the server, receive (h, h_bar), update their local
block, repeat. A party's simulated compute cost is an explicit sleep
proportional to its block dimension (so q-party runs genuinely parallelize,
reproducing Fig 4's near-linear speedup), and stragglers get a slowdown
multiplier (Fig 3's async-vs-sync efficiency).

The synchronous executor (SynREVEL) runs the same math but with a barrier
per round — every party waits for the slowest. ``run_serial`` is the
deterministic reference schedule (round-robin, single thread) used for
transcripts, replay, and the bit-identity regression.

The message round itself (perturbation, up-link codec, coefficient, update
apply) is the SAME core/exchange.py ZOExchange the device-scan trainer in
asyrevel.py uses — including the optional DP defense (``VFLConfig.dp``,
src/repro/dp): ``encode_up`` clips-then-noises every upload before the
codec, keyed off the same per-round keys, so defended runs stay
bit-identical across the memory and TCP transports. Every boundary crossing is a typed ``core/wire.py``
Message routed through the trainer's ``Channel``:

    party m --c_up, c_hat_up (xK)--> server --loss_down (h, h_bar_1..K)--> m

With the default ``InMemoryChannel`` transport is free and runs are
bit-identical to the pre-wire executor (pinned in tests/test_wire.py); a
``NetworkChannel`` prices each message with a per-link latency/bandwidth/
jitter clock (``realtime=True`` also sleeps it, replacing ad-hoc sleep
modelling of the wire); a ``RecordingChannel`` captures the transcript the
privacy attacks in core/privacy.py consume. Byte counters are measured
twice independently — by the exchange's ``CommsMeter`` at the codec and by
the channel per message kind — and tests assert they agree.

This module reproduces the paper's wall-clock experiments faithfully at the
paper's own scale; the jit/scan trainer in asyrevel.py is the TPU-scale
adaptation of the same update process.
"""
from __future__ import annotations

import functools
import threading
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import VFLConfig
from repro.core.exchange import CommsMeter, ZOExchange, wire_nbytes
from repro.core.vfl import VFLModel
from repro.kernels import fused_round
from repro.core.wire import (SERVER, Channel, InMemoryChannel, Message,
                             party, party_index)
from repro.obs import maybe_tracer, trace
from repro.utils.prng import fold_name

# This container has ONE core: concurrent XLA-CPU executions from many
# threads thrash (dispatch contention blows sub-ms calls up to ~100ms).
# All jax work is serialized behind one device lock; the PARALLEL part of
# the simulation is the sleep-modelled party compute — exactly the real
# deployment, where each party owns its own machine and only the tiny
# function-value messages serialize at the server.
_JAX_LOCK = threading.Lock()


@dataclass
class HostRunResult:
    history: list = field(default_factory=list)   # (wallclock_s, loss)
    updates: int = 0
    comms: CommsMeter = field(default_factory=CommsMeter)
    channel: Channel | None = None                # the run's wire

    # Transport counters are PER ROUND, measured from the encoded wire
    # arrays by the shared ZOExchange: up = the c payload plus one c_hat
    # per direction, down = the (h, h_bar_1..K) scalars — the server
    # replies batch-MEAN losses, so the down-link is (1+K) * 4 bytes per
    # round, NOT per sample.
    @property
    def bytes_up(self) -> int:
        return self.comms.up_bytes

    @property
    def bytes_down(self) -> int:
        return self.comms.down_bytes

    def time_to_loss(self, target: float):
        for t, lo in self.history:
            if lo <= target:
                return t
        return None


@functools.partial(jax.jit, static_argnames=("model", "vfl"))
def _serve_jit(model, vfl, w0, cs, cs_hat, y, key):
    """Fused Algorithm-1 server side: one dispatch per round keeps the
    lock's critical section short. Eq. 17 routes through the exchange."""
    ex = ZOExchange.from_config(vfl)
    h = model.server_forward(w0, cs, y)
    h_bar = model.server_forward(w0, cs_hat, y)
    if vfl.perturb_server:
        w0 = ex.server_update(w0, key, h,
                              lambda w0p: model.server_forward(w0p, cs, y),
                              vfl.lr_server)
    return h, h_bar, w0


@functools.partial(jax.jit, static_argnames=("model", "vfl"))
def _serve_k_jit(model, vfl, w0, cs, c_hats, y, key, m):
    """K-direction server side: h plus one h_bar per received c_hat
    (c_hats stacked (K, B)); the server's own Eq. 17 update is unchanged
    (it re-evaluates on the base cs)."""
    ex = ZOExchange.from_config(vfl)
    h = model.server_forward(w0, cs, y)
    h_bars = jax.vmap(
        lambda ch: model.server_forward(w0, cs.at[:, m].set(ch), y))(c_hats)
    if vfl.perturb_server:
        w0 = ex.server_update(w0, key, h,
                              lambda w0p: model.server_forward(w0p, cs, y),
                              vfl.lr_server)
    return h, h_bars, w0


@functools.partial(jax.jit, static_argnames=("model", "vfl", "m"))
def _party_fused_jit(model, vfl, w_m, x_m, key, m):
    """One dispatch: perturb + both local evals + both regs."""
    ex = ZOExchange.from_config(vfl)
    w_p, u = ex.perturb(w_m, key)
    c = model.party_forward(w_m, x_m, m)
    c_hat = model.party_forward(w_p, x_m, m)
    return c, c_hat, model.regularizer(w_m), model.regularizer(w_p), u


@functools.partial(jax.jit, static_argnames=("model", "vfl", "m"))
def _party_fused_k_jit(model, vfl, w_m, x_m, key, m):
    """K-direction party side: the K perturbed blocks are stacked and the
    local evals vmapped — one dispatch, K c_hat payloads (mirrors
    ZOExchange.party_gradient's batched multi-direction round)."""
    ex = ZOExchange.from_config(vfl)
    keys = jax.random.split(key, vfl.num_directions)
    w_ps, us = jax.vmap(lambda k: ex.perturb(w_m, k))(keys)
    c = model.party_forward(w_m, x_m, m)
    c_hats = jax.vmap(lambda w_p: model.party_forward(w_p, x_m, m))(w_ps)
    regs = jax.vmap(model.regularizer)(w_ps)
    return c, c_hats, model.regularizer(w_m), regs, us, keys


@functools.partial(jax.jit, static_argnames=("model", "vfl", "ex", "m"))
def _party_release_jit(model, vfl, ex, w_m, x_m, key, z, m):
    """The whole fused party round in ONE dispatch: perturb + both local
    evals + the defended encode (clip -> dp noise -> codec) of both
    up-link payloads. The baseline f32 exchange encodes for free (its
    codec is a passthrough), so folding the defended encodes into the
    party dispatch is what puts the defended round at dispatch parity
    with the plain protocol. Key discipline and bits are EXACTLY the
    two-call path below (the z runtime-zero guards in kernels/fused_round
    hold in this larger co-optimized graph too — pinned at run level in
    tests/test_kernels.py)."""
    c, c_hat, reg0, reg1, u = _party_fused_jit(model, vfl, w_m, x_m, key, m)
    wire_c = fused_round._encode_up_jit(
        ex, c, jax.random.fold_in(key, 1), z, "xla", True)
    wire_c_hat = fused_round._encode_up_jit(
        ex, c_hat, jax.random.fold_in(key, 2), z, "xla", True)
    return wire_c, wire_c_hat, reg0, reg1, u


@functools.partial(jax.jit, static_argnames=("model", "vfl", "ex", "m"))
def _party_release_k_jit(model, vfl, ex, w_m, x_m, key, z, m):
    """K-direction twin of _party_release_jit: one dispatch yields the
    base wire plus one independently-keyed wire per direction (same
    fold_name(k_dir, 'codec_hat') schedule as the unfused path)."""
    c, c_hats, reg0, regs_k, us, keys = _party_fused_k_jit(
        model, vfl, w_m, x_m, key, m)
    wire_c = fused_round._encode_up_jit(
        ex, c, jax.random.fold_in(key, 1), z, "xla", True)
    wire_hats = tuple(
        fused_round._encode_up_jit(
            ex, c_hats[k], fold_name(keys[k], "codec_hat"), z, "xla", True)
        for k in range(vfl.num_directions))
    return wire_c, wire_hats, reg0, regs_k, us


@functools.partial(jax.jit, static_argnames=("vfl",))
def _party_apply_jit(vfl, w_m, u, coeff):
    return ZOExchange.from_config(vfl).apply_direction(
        w_m, u, coeff, vfl.lr_party)


@functools.partial(jax.jit, static_argnames=("vfl",))
def _party_apply_k_jit(vfl, w_m, us, coeffs):
    """K-direction averaged update: w_m - lr * mean_k coeff_k * u_k."""
    g = ZOExchange.mean_direction(coeffs, us)
    return jax.tree.map(
        lambda a, gg: (a - vfl.lr_party * gg).astype(a.dtype), w_m, g)


# ---- the party-side round, split at the wire boundary ---------------------
#
# HostAsyncTrainer.party_step = prepare -> send up -> (server) -> apply.
# The multi-process runtime (repro/runtime/party.py) runs the SAME three
# helpers with a TCP socket between send and apply, so a TCP run is
# bit-identical to run_serial by construction — there is exactly one
# implementation of the party math.

def trainer_keys(seed: int, q: int):
    """The key split every executor shares: (server_init, party_inits[q],
    server_perturbation_stream)."""
    keys = jax.random.split(jax.random.key(seed), q + 2)
    return keys[0], [keys[m + 1] for m in range(q)], keys[q + 1]


def party_rng_seed(seed: int, m: int) -> int:
    """Party m's private numpy stream (batch sampling + round keys)."""
    return seed * 97 + m


def draw_round(rng: np.random.Generator, n: int, batch_size: int):
    """One round's (batch indices, perturbation key) — two draws, in this
    exact order, so a resuming party can fast-forward its stream by
    replaying completed rounds."""
    idx = rng.integers(0, n, batch_size)
    key = jax.random.key(rng.integers(1 << 31))
    return idx, key


@dataclass
class PartyRoundPrep:
    """Everything party m derives locally for one round: the encoded
    up-link payloads plus the private state the apply step needs."""

    wire_c: object
    wire_hats: list
    reg0: float
    regs: list
    us: object            # u tree (K=1) or stacked u trees (K>1)


def party_round_prepare(model, vfl: VFLConfig, ex: ZOExchange, w_m, X,
                        idx, key, m: int) -> PartyRoundPrep:
    """Perturb/evaluate locally and encode the up-link payloads (the
    compute half of Algorithm 1's party round — no wire crossing).
    Span: ``party_prepare`` — the release-jit dispatch time."""
    with trace("party_prepare", party=int(m)):
        return _party_round_prepare(model, vfl, ex, w_m, X, idx, key, m)


def _party_round_prepare(model, vfl, ex, w_m, X, idx, key, m):
    idx = np.asarray(idx)
    if vfl.num_directions == 1:
        with _JAX_LOCK:
            x_m = model.slice_features(jnp.asarray(X[idx]), m)
            if ex.fused:
                # single dispatch for compute AND both defended encodes
                # (the exchange rides as a static arg — it hashes by
                # semantics and the traced code never touches its meter)
                wire_c, wire_c_hat, reg0, reg1, u = _party_release_jit(
                    model, vfl, ex, w_m, x_m, key,
                    fused_round.runtime_zero(), m)
                if ex.meter is not None:
                    ex.meter.add_up(wire_nbytes(wire_c))
                    ex.meter.add_up(wire_nbytes(wire_c_hat))
            else:
                c, c_hat, reg0, reg1, u = _party_fused_jit(
                    model, vfl, w_m, x_m, key, m)
                wire_c = ex.encode_up(c, jax.random.fold_in(key, 1))
                wire_c_hat = ex.encode_up(c_hat, jax.random.fold_in(key, 2))
            wire_c = jax.tree.map(np.asarray, wire_c)
            wire_hats = [jax.tree.map(np.asarray, wire_c_hat)]
            regs = [float(reg1)]
            us = u
    else:
        with _JAX_LOCK:
            x_m = model.slice_features(jnp.asarray(X[idx]), m)
            if ex.fused:
                wire_c, wire_hats_j, reg0, regs_k, us = _party_release_k_jit(
                    model, vfl, ex, w_m, x_m, key,
                    fused_round.runtime_zero(), m)
                if ex.meter is not None:
                    ex.meter.add_up(wire_nbytes(wire_c))
                    for w in wire_hats_j:
                        ex.meter.add_up(wire_nbytes(w))
                wire_c = jax.tree.map(np.asarray, wire_c)
                wire_hats = [jax.tree.map(np.asarray, w)
                             for w in wire_hats_j]
            else:
                c, c_hats, reg0, regs_k, us, keys = _party_fused_k_jit(
                    model, vfl, w_m, x_m, key, m)
                wire_c = ex.encode_up(c, jax.random.fold_in(key, 1))
                wire_c = jax.tree.map(np.asarray, wire_c)
                # each direction's upload is its OWN message with its own
                # rounding key (fold_name(k_dir, 'codec_hat'), matching
                # the device-scan path's per-direction independence)
                wire_hats = [
                    jax.tree.map(np.asarray, ex.encode_up(
                        c_hats[k], fold_name(keys[k], "codec_hat")))
                    for k in range(vfl.num_directions)]
            regs = [float(r) for r in np.asarray(regs_k)]
    return PartyRoundPrep(wire_c, wire_hats, float(reg0), regs, us)


def party_round_messages(channel: Channel, m: int, rnd: int, idx,
                         prep: PartyRoundPrep):
    """Route the round's up-link through the (local) channel stack and
    return the delivered Messages."""
    idx = np.asarray(idx)
    me = party(m)
    msg_c = channel.send(Message.make(
        "c_up", me, SERVER, rnd, prep.wire_c, meta={"idx": idx}))
    msg_hats = tuple(channel.send(Message.make(
        "c_hat_up", me, SERVER, rnd, w, meta={"idx": idx, "dir": k}))
        for k, w in enumerate(prep.wire_hats))
    return msg_c, msg_hats


def party_round_apply(vfl: VFLConfig, ex: ZOExchange, w_m,
                      prep: PartyRoundPrep, scalars):
    """Form the two-point coefficient(s) from the received loss_down
    scalars and apply the block update (Algorithm 1 line 7)."""
    h, *h_bars = scalars
    if vfl.num_directions == 1:
        coeff = ex.coefficient(h_bars[0] + vfl.lam * prep.regs[0],
                               h + vfl.lam * prep.reg0)
        with _JAX_LOCK:
            return _party_apply_jit(vfl, w_m, prep.us, coeff)
    coeffs = jnp.asarray([
        ex.coefficient(h_bars[k] + vfl.lam * prep.regs[k],
                       h + vfl.lam * prep.reg0)
        for k in range(vfl.num_directions)], jnp.float32)
    with _JAX_LOCK:
        return _party_apply_k_jit(vfl, w_m, prep.us, coeffs)


class _Server:
    """Holds w0 + the latest c table; all access behind one lock (the MPI
    process would serialize the same way). Receives the party's typed
    up-link Messages (codec-encoded payloads), decodes through the shared
    exchange, and replies with a loss_down Message through the channel —
    the measured byte counters are the real wire sizes."""

    def __init__(self, model: VFLModel, vfl: VFLConfig, n: int, key,
                 ex: ZOExchange, pert_key, channel: Channel):
        self.model = model
        self.vfl = vfl
        self.ex = ex
        self.channel = channel
        # reentrant: the TCP runtime wraps handle() plus its own reply
        # bookkeeping in ONE critical section (snapshot atomicity), and
        # handle() takes this lock again internally
        self.lock = threading.RLock()
        self.w0 = model.init_server(key)          # guarded-by: self.lock
        # the server's own perturbation stream derives from the TRAINER
        # seed (folded per update in handle) — a constant base key here
        # would replay the identical direction sequence for every seed
        self.pert_key = pert_key
        # latest function value of each party on each sample ("received
        # previously", Algorithm 1) — warm-started to zeros.
        self.c_table = np.zeros(                  # guarded-by: self.lock
            (n, model.num_parties), np.float32)
        self.losses = HostRunResult(              # guarded-by: self.lock
            comms=ex.meter, channel=channel)
        # update-budget claims (run_async): taken under self.lock BEFORE a
        # party starts its round, so a run does exactly total_updates
        # updates instead of racing past the budget by up to q-1 rounds
        self.claimed = 0                          # guarded-by: self.lock
        # re-stamped by HostAsyncTrainer at run start so history holds
        # run-relative wall-clock (construction-time stamping counted jit
        # warm-up into Fig 3/4's time-to-loss)
        self.t0 = time.perf_counter()

    def handle(self, msg_c: Message, msg_c_hats, update_w0: bool = True):
        """Algorithm 1 lines 8-11. Takes the delivered c_up Message plus
        the tuple of c_hat_up Messages (one per direction), returns the
        delivered loss_down Message carrying the (h, h_bar_1..K) scalars.
        Byte accounting: up = measured size of the encoded payloads
        (metered at encode_up AND per-kind on the channel), down =
        (1+K) scalars per ROUND (batch-mean losses).

        Span: ``server_handle`` keyed on the PARTY round (``msg_c.round``)
        so the collector can join it against the party's own spans and
        the c_up crossing; a defended round also charges its releases
        (1 + K) to the tracer's epsilon-spend accountant."""
        if isinstance(msg_c_hats, Message):
            msg_c_hats = (msg_c_hats,)
        with trace("server_handle", party=party_index(msg_c.sender),
                   round=int(msg_c.round)):
            down = self._handle(msg_c, msg_c_hats, update_w0)
        tr = maybe_tracer()
        if tr is not None:
            tr.dp_round(self.ex.dp, releases=1 + len(msg_c_hats),
                        party=party_index(msg_c.sender))
            # the round's loss as a gauge: the health plane's divergence
            # detector reads it live (scalars()[0] is h — already a
            # float, no extra device sync)
            tr.gauge("loss", float(down.scalars()[0]),
                     party=party_index(msg_c.sender),
                     round=int(msg_c.round))
        return down

    def _handle(self, msg_c: Message, msg_c_hats, update_w0: bool):
        m = party_index(msg_c.sender)
        idx = msg_c.meta["idx"]
        with self.lock:
            rnd = self.losses.updates
            key = jax.random.fold_in(self.pert_key, rnd)
            if len(msg_c_hats) == 1:
                with _JAX_LOCK:
                    c = np.asarray(self.ex.decode_up(msg_c.payload),
                                   np.float32)
                    c_hat = jnp.asarray(
                        self.ex.decode_up(msg_c_hats[0].payload))
                self.c_table[idx, m] = c
                cs = jnp.asarray(self.c_table[idx])      # stale others
                cs_hat = cs.at[:, m].set(c_hat)
                y = self.y[idx]
                with _JAX_LOCK:
                    h, h_bar, w0 = _serve_jit(self.model, self.vfl,
                                              self.w0, cs, cs_hat, y, key)
                    h, h_bar = float(h), float(h_bar)
                h_bars = (h_bar,)
            else:
                with _JAX_LOCK:
                    c = np.asarray(self.ex.decode_up(msg_c.payload),
                                   np.float32)
                    c_hats = jnp.stack([
                        jnp.asarray(self.ex.decode_up(mm.payload))
                        for mm in msg_c_hats])
                self.c_table[idx, m] = c
                cs = jnp.asarray(self.c_table[idx])
                y = self.y[idx]
                with _JAX_LOCK:
                    h, h_bars, w0 = _serve_k_jit(self.model, self.vfl,
                                                 self.w0, cs, c_hats, y,
                                                 key, m)
                    h = float(h)
                    h_bars = tuple(float(x) for x in np.asarray(h_bars))
            if update_w0:
                self.w0 = w0
            self.losses.updates += 1
            self.losses.history.append(
                (time.perf_counter() - self.t0, h))
            self.ex.meter.add_round()
            payload = self.ex.send_down(h, *h_bars)      # meters the bytes
            down = Message.make("loss_down", SERVER, msg_c.sender, rnd,
                                payload)
            return self.channel.send(down)


class HostAsyncTrainer:
    """AsyREVEL over threads (``run_async``), the synchronous SynREVEL
    with a per-round barrier (``run_sync``), or the deterministic
    round-robin reference schedule (``run_serial``)."""

    def __init__(self, model: VFLModel, vfl: VFLConfig, X, y,
                 batch_size: int = 32, compute_cost_s: float = 2e-4,
                 straggler: dict[int, float] | None = None, seed: int = 0,
                 channel: Channel | None = None):
        self.model, self.vfl = model, vfl
        self.X = np.asarray(X)
        self.y = np.asarray(y)
        self.batch_size = batch_size
        self.compute_cost_s = compute_cost_s
        self.straggler = straggler or {}
        self.seed = seed
        self.channel = channel if channel is not None else InMemoryChannel()
        self.exchange = ZOExchange.from_config(vfl, meter=CommsMeter())
        q = model.num_parties
        server_key, party_keys, pert_key = trainer_keys(seed, q)
        self.server = _Server(model, vfl, len(self.y), server_key,
                              self.exchange, pert_key=pert_key,
                              channel=self.channel)
        self.server.y = jnp.asarray(self.y)
        self.party_w = [model.init_party(party_keys[m], m)
                        for m in range(q)]
        self._party_round = [0] * q
        self._spent = False

    def _warm_jits(self):
        """Execute every per-(shape, party) jit once on dummy data so the
        compiles land BEFORE the run clock starts — re-stamping t0 alone
        would still leak the first round's compile time into
        history[0]."""
        vfl, q = self.vfl, self.model.num_parties
        idx = np.arange(self.batch_size) % len(self.y)
        key = jax.random.key(0)
        # server.lock is vacuously uncontended here (workers spawn later)
        # but taking it keeps one lock order everywhere: server before jax
        with self.server.lock, _JAX_LOCK:
            cs = jnp.asarray(self.server.c_table[idx])
            y = self.server.y[idx]
            ex, z = self.exchange, fused_round.runtime_zero()
            for m in range(q):
                x_m = self.model.slice_features(jnp.asarray(self.X[idx]), m)
                if vfl.num_directions == 1:
                    c, c_hat, _, _, u = _party_fused_jit(
                        self.model, vfl, self.party_w[m], x_m, key, m)
                    if ex.fused:
                        _party_release_jit(self.model, vfl, ex,
                                           self.party_w[m], x_m, key, z, m)
                    if m == 0:  # party blocks share structure/shapes
                        _serve_jit(self.model, vfl, self.server.w0, cs,
                                   cs.at[:, m].set(c_hat), y, key)
                        _party_apply_jit(vfl, self.party_w[m], u, 0.0)
                else:
                    c, c_hats, _, regs, us, _ = _party_fused_k_jit(
                        self.model, vfl, self.party_w[m], x_m, key, m)
                    if ex.fused:
                        _party_release_k_jit(self.model, vfl, ex,
                                             self.party_w[m], x_m, key, z, m)
                    if m == 0:
                        _serve_k_jit(self.model, vfl, self.server.w0, cs,
                                     c_hats, y, key, m)
                        _party_apply_k_jit(vfl, self.party_w[m], us,
                                           jnp.zeros_like(regs))

    def _start_run(self):
        """Arm one run: history timestamps are RUN-relative (everything
        before the first real round — jit compiles, data device-puts —
        must not pollute Fig 3/4's time-to-loss), and a trainer only runs
        once (its optimizer state, c table, and meters are mid-trajectory
        after a run; reusing them silently would corrupt comparisons)."""
        if self._spent:
            raise RuntimeError(
                "this HostAsyncTrainer already ran; construct a fresh one "
                "(history/meters are run-relative)")
        self._spent = True
        self._warm_jits()
        self.server.t0 = time.perf_counter()

    # ---- one party-side round (shared by all executors) ------------------
    def party_step(self, m: int, idx: np.ndarray, key):
        """Deterministic core of one Algorithm-1 round for party m on the
        given batch: perturb/eval locally, encode + send the c_up and
        c_hat_up Messages, receive the loss_down Message, form the
        coefficient(s), apply the block update. `key` drives the
        perturbation direction (and, for the stochastic codec, the
        rounding). The three halves are the module-level helpers above so
        the TCP runtime runs the identical math."""
        rnd = self._party_round[m]
        self._party_round[m] += 1
        with trace("party_round", party=int(m), round=int(rnd)):
            prep = party_round_prepare(self.model, self.vfl, self.exchange,
                                       self.party_w[m], self.X, idx, key, m)
            # simulated local compute cost (scales with the block dim)
            t = self.compute_cost_s * self.straggler.get(m, 1.0)
            if t > 0:
                time.sleep(t)
            msg_c, msg_hats = party_round_messages(self.channel, m, rnd,
                                                   idx, prep)
            down = self.server.handle(msg_c, msg_hats)
            self.party_w[m] = party_round_apply(self.vfl, self.exchange,
                                                self.party_w[m], prep,
                                                down.scalars())

    def _party_update(self, m: int, rng: np.random.Generator):
        idx, key = draw_round(rng, len(self.y), self.batch_size)
        self.party_step(m, idx, key)

    def _claim_update(self, total_updates: int) -> bool:
        """Reserve one unit of the global update budget under the server
        lock. Checking ``losses.updates`` unlocked let all q parties pass
        the gate at updates == total-1 and overshoot by up to q-1 rounds;
        a claim is taken BEFORE the round starts, so exactly
        ``total_updates`` rounds ever begin."""
        with self.server.lock:
            if self.server.claimed >= total_updates:
                return False
            self.server.claimed += 1
            return True

    def run_async(self, total_updates: int) -> HostRunResult:
        """Parties run until the GLOBAL update budget is spent — fast
        parties naturally contribute more rounds (this is precisely why
        async wins with stragglers: nobody waits)."""
        self._start_run()
        q = self.model.num_parties
        threads = []

        def loop(m):
            rng = np.random.default_rng(party_rng_seed(self.seed, m))
            while self._claim_update(total_updates):
                self._party_update(m, rng)

        for m in range(q):
            th = threading.Thread(target=loop, args=(m,), daemon=True)
            threads.append(th)
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        # zvlint: disable=lock-discipline — all writers joined above
        return self.server.losses

    def run_sync(self, rounds: int) -> HostRunResult:
        """Barrier per round: parties run concurrently but the round only
        finishes when the slowest party (the straggler) does. One
        PERSISTENT worker per party synchronized on a ``Barrier`` — the
        old spawn-q-threads-per-round loop charged thread churn to the
        SynREVEL wall-clock it reports."""
        self._start_run()
        q = self.model.num_parties
        barrier = threading.Barrier(q)
        errors: list[BaseException] = []

        def worker(m):
            rng = np.random.default_rng(party_rng_seed(self.seed, m))
            for _ in range(rounds):
                try:
                    self._party_update(m, rng)
                    barrier.wait()       # <- synchronization cost
                except threading.BrokenBarrierError:
                    return
                except BaseException as e:  # noqa: BLE001
                    errors.append(e)
                    barrier.abort()      # release the other workers
                    return

        threads = [threading.Thread(target=worker, args=(m,), daemon=True)
                   for m in range(q)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        if errors:
            raise errors[0]
        # zvlint: disable=lock-discipline — all writers joined above
        return self.server.losses

    def run_serial(self, rounds: int) -> HostRunResult:
        """Deterministic reference schedule: single thread, each round
        visits every party in index order. Threaded runs interleave
        server arrivals nondeterministically; this schedule never does,
        so it is the one transcripts, replays, and the bit-identity
        regression are pinned against."""
        self._start_run()
        q = self.model.num_parties
        rngs = [np.random.default_rng(party_rng_seed(self.seed, m))
                for m in range(q)]
        for _ in range(rounds):
            for m in range(q):
                self._party_update(m, rngs[m])
        # zvlint: disable=lock-discipline — single-threaded schedule
        return self.server.losses
