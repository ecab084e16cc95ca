"""AsyREVEL / SynREVEL — device-level trainers (Algorithm 1).

This is the TPU/SPMD adaptation of the paper's MPI asynchrony (DESIGN.md §4):
a single ``lax.scan`` carries

  * the party params stacked over a leading q axis,
  * a (tau+1)-slot ring buffer of PAST party params — at step t the
    activated party m_t ~ Categorical(p) (Assumption 3) sees the OTHER
    parties' outputs computed from params delayed by tau_j <= tau
    (Assumption 4: w_bar = w^{t - tau_t}),
  * the server params w_0.

Each step performs exactly the paper's message pattern:
  party m uploads (c_m, c_hat_m); the server computes h, h_bar, h_hat and
  returns (h, h_bar); party m forms the two-point estimate and updates w_m;
  the server forms Eq. (17) and updates w_0. Nothing but function values
  crosses the party/server boundary — the round itself (perturb, payload
  codec, coefficient, apply) lives in core/exchange.py's ZOExchange, so
  the boundary is enforced in ONE place shared with the host executor and
  zo_sgd: the party update consumes only scalars + its own state, and the
  up-link payload goes through the configured codec (vfl.codec).

The host-level REAL asynchronous executor (threads, stragglers, wall-clock)
lives in core/async_host.py; this module is the jit-able scale path and the
object of the convergence theorems.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import VFLConfig
from repro.core.exchange import ZOExchange
from repro.core.vfl import VFLModel
from repro.utils.prng import fold_name


class AsyState(NamedTuple):
    w0: dict
    parties: dict          # stacked (q, ...)
    hist: dict             # ring buffer (tau+1, q, ...)
    step: jnp.ndarray
    key: jnp.ndarray


def _gather_party(tree, m):
    return jax.tree.map(lambda a: a[m], tree)


def _stale_parties(hist, slots):
    """hist leaves: (tau+1, q, ...); slots: (q,) int -> (q, ...) params."""
    q = slots.shape[0]
    return jax.tree.map(
        lambda h: h[slots, jnp.arange(q)], hist)


def init_state(model: VFLModel, vfl: VFLConfig, key) -> AsyState:
    k0, k1 = jax.random.split(key)
    w0 = model.init_server(k0)
    parties = model.init_parties_stacked(k1)
    hist = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (vfl.max_delay + 1,) + a.shape),
        parties)
    return AsyState(w0, parties, hist, jnp.zeros((), jnp.int32), key)


def _activation_probs(vfl: VFLConfig):
    if vfl.activation_probs is not None:
        p = jnp.asarray(vfl.activation_probs, jnp.float32)
        return p / p.sum()
    return jnp.full((vfl.num_parties,), 1.0 / vfl.num_parties)


def asyrevel_step(model: VFLModel, vfl: VFLConfig, state: AsyState, batch,
                  ex: ZOExchange | None = None):
    """One AsyREVEL iteration (Algorithm 1 lines 2-11)."""
    ex = ex if ex is not None else ZOExchange.from_config(vfl)
    q, tau = vfl.num_parties, vfl.max_delay
    key = jax.random.fold_in(state.key, state.step)
    k_m, k_d, k_u, k_u0, k_c = (fold_name(key, s)
                                for s in ("party", "delay", "u", "u0",
                                          "codec"))
    x = model.party_args(batch)
    y = model.server_args(batch)

    # --- Assumption 3: activated party; Assumption 4: bounded delays -----
    m_t = jax.random.categorical(k_m, jnp.log(_activation_probs(vfl)))
    delays = jax.random.randint(k_d, (q,), 0, tau + 1)
    delays = delays.at[m_t].set(0)         # a party's own params are fresh
    # w^{t-delta} = params after step t-1-delta; hist[s] holds the params
    # written at the end of the latest step with step % (tau+1) == s.
    slots = (state.step - 1 - delays) % (tau + 1)
    stale = _stale_parties(state.hist, slots)

    # --- step 4-5: party m computes c_m, c_hat_m on PRIVATE data; the c
    # table the server holds is what survived the up-link codec, one
    # MESSAGE (party) at a time — each party's upload is its own tensor
    # with its own codec scale, matching the host executor's wire --------
    cs = model.all_party_outputs(stale, x)                  # stale c's
    cs = model.map_party_outputs(
        cs, lambda c, m: ex.roundtrip_up(c, jax.random.fold_in(k_c, m)))
    w_m = _gather_party(state.parties, m_t)
    x_m = model.slice_features(x, m_t)
    h = model.server_forward(state.w0, cs, y)               # h_{i,m}
    reg0 = model.regularizer(w_m)

    # one or several directions (num_directions > 1 = variance-reduced
    # averaging, beyond-paper). K directions are ONE batched round: the
    # exchange stacks the K perturbed blocks and vmaps this closure, so
    # the K c_hat uploads fuse into a single multi-direction dispatch —
    # still only function values. k_dir is the direction's own subkey;
    # folding it into the codec key gives each upload an INDEPENDENT
    # stochastic-rounding draw (shared noise would defeat the K-direction
    # variance reduction).
    def f_of(w_m_pert, k_dir):
        c_hat = model.party_forward(w_m_pert, x_m, m_t)
        c_hat = ex.roundtrip_up(c_hat, fold_name(k_dir, "codec_hat"))
        cs_hat = model.replace_party_output(cs, c_hat, m_t)
        h_bar = model.server_forward(state.w0, cs_hat, y)   # h-bar_{i,m}
        return h_bar + vfl.lam * model.regularizer(w_m_pert)

    g_m = ex.party_gradient(w_m, k_u, h + vfl.lam * reg0, f_of)

    # --- step 6-7: party update (Eq. 15) ----------------------------------
    parties = ex.apply_block(state.parties, m_t, g_m, vfl.lr_party)

    # --- step 9-11: server's own estimate + update (Eq. 17) ---------------
    if vfl.perturb_server:
        w0 = ex.server_update(
            state.w0, k_u0, h,
            lambda w0p: model.server_forward(w0p, cs, y),   # h-hat_{i,m}
            vfl.lr_server)
    else:
        w0 = state.w0

    hist = jax.tree.map(
        lambda hbuf, p: hbuf.at[state.step % (tau + 1)].set(p),
        state.hist, parties)
    new_state = AsyState(w0, parties, hist, state.step + 1, state.key)
    return new_state, h


def synrevel_step(model: VFLModel, vfl: VFLConfig, state: AsyState, batch,
                  ex: ZOExchange | None = None):
    """Synchronous counterpart: every round ALL parties (and the server)
    compute fresh c's, perturb, and update together — no staleness."""
    ex = ex if ex is not None else ZOExchange.from_config(vfl)
    q = vfl.num_parties
    key = jax.random.fold_in(state.key, state.step)
    k_c = fold_name(key, "codec")
    x = model.party_args(batch)
    y = model.server_args(batch)
    cs = model.all_party_outputs(state.parties, x)
    cs = model.map_party_outputs(
        cs, lambda c, m: ex.roundtrip_up(c, jax.random.fold_in(k_c, m)))
    h = model.server_forward(state.w0, cs, y)

    new_parties = state.parties
    for m in range(q):
        k_u = fold_name(key, f"u{m}")
        w_m = _gather_party(state.parties, m)

        def f_of(w_m_pert, k_dir, m=m):
            c_hat = model.party_forward(
                w_m_pert, model.slice_features(x, m), m)
            # k_dir already encodes the party (derived from k_u) AND the
            # direction, so every upload gets its own rounding draw
            c_hat = ex.roundtrip_up(c_hat, fold_name(k_dir, "codec_hat"))
            h_bar = model.server_forward(
                state.w0, model.replace_party_output(cs, c_hat, m), y)
            return h_bar + vfl.lam * model.regularizer(w_m_pert)

        g_m = ex.party_gradient(
            w_m, k_u, h + vfl.lam * model.regularizer(w_m), f_of)
        new_parties = ex.apply_block(new_parties, m, g_m, vfl.lr_party)

    if vfl.perturb_server:
        w0 = ex.server_update(
            state.w0, fold_name(key, "u0"), h,
            lambda w0p: model.server_forward(w0p, cs, y), vfl.lr_server)
    else:
        w0 = state.w0
    new_state = AsyState(w0, new_parties, state.hist, state.step + 1,
                         state.key)
    return new_state, h


@functools.partial(jax.jit, static_argnames=("model", "vfl", "steps",
                                             "batch_size", "algorithm"))
def train(model: VFLModel, vfl: VFLConfig, data, key, steps: int,
          batch_size: int, algorithm: str = "asyrevel"):
    """Scan `steps` iterations over random minibatches of `data`.

    data: pytree of arrays with a shared leading sample dim.
    Returns (final_state, per-step losses).
    """
    n = jax.tree.leaves(data)[0].shape[0]
    state = init_state(model, vfl, key)
    step_fn = asyrevel_step if algorithm == "asyrevel" else synrevel_step
    ex = ZOExchange.from_config(vfl)

    def body(state, k):
        idx = jax.random.randint(k, (batch_size,), 0, n)
        batch = jax.tree.map(lambda a: a[idx], data)
        return step_fn(model, vfl, state, batch, ex)

    keys = jax.random.split(jax.random.fold_in(key, 7), steps)
    state, losses = jax.lax.scan(body, state, keys)
    return state, losses


# ------------------------------------------------- sharded scale path -----

class PmeanVFLModel:
    """Data-parallel view of a VFLModel inside a ``shard_map`` body.

    Every method delegates to the wrapped model; only ``server_forward``
    changes — it returns the GLOBAL batch-mean loss via ``lax.pmean``
    over the data axis, so the two-point coefficients every party (and
    the server) forms are identical on all devices and the replicated
    parameter trees stay bitwise in sync without any parameter
    collectives. The c values themselves never cross devices: each shard
    uploads its own slice of the batch and only the scalar losses are
    psum-reduced — the same function-values-only boundary, now also the
    only cross-DEVICE traffic (see docs/scale.md).
    """

    def __init__(self, inner: VFLModel, axis_name: str):
        self.inner = inner
        self.axis_name = axis_name
        self.num_parties = inner.num_parties

    def server_forward(self, w0, cs, y):
        return jax.lax.pmean(self.inner.server_forward(w0, cs, y),
                             self.axis_name)

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def _hash_key(self):
        return (type(self).__name__, self.inner._hash_key(), self.axis_name)

    def __hash__(self):
        return hash(self._hash_key())

    def __eq__(self, other):
        return (type(other) is PmeanVFLModel
                and self._hash_key() == other._hash_key())


class ShardFoldedExchange(ZOExchange):
    """ZOExchange for a shard_map body with dp > 1: folds the device's
    data-axis index into the codec rounding key, so the dp per-shard
    slices of one upload carry INDEPENDENT stochastic-rounding draws —
    the per-direction independence fix, applied along the shard axis
    (the replicated step key would otherwise hand every shard the same
    noise realization). The DP-noise stream folds the same way (the
    base's ``dp`` config is inherited and ``_dp_key`` routes through
    ``_codec_key``), so per-shard slices of a defended upload are
    independent releases. Only constructed for dp > 1: fold_in(key, 0)
    is not the identity, so using it on a 1-device mesh would break the
    bit-parity with the single-device scan."""

    def __init__(self, base: ZOExchange, axis_name: str):
        super().__init__(mu=base.mu, direction=base.direction,
                         lam=base.lam, num_directions=base.num_directions,
                         seed_replay=base.seed_replay, codec=base.codec,
                         meter=None, dp=base.dp, fused=base.fused)
        self.axis_name = axis_name

    def _codec_key(self, key):
        if key is None:
            return None
        return jax.random.fold_in(key, jax.lax.axis_index(self.axis_name))

    def _hash_key(self):
        return (type(self).__name__, self.axis_name,
                super()._hash_key())


def shard_wrap(model: VFLModel, ex: ZOExchange, mesh,
               data_axis: str = "data"):
    """The one place the sharded-body wrapping is decided: returns
    ``(pmodel, ex, dp)`` — the pmean model view and, ONLY when the data
    axis is wider than one device, the shard-folded exchange. The dp > 1
    gate is load-bearing: fold_in(key, 0) is not the identity, so
    wrapping on a 1-device mesh would break bit-parity with the
    single-device scan. Both sharded entry points
    (``make_sharded_train_fn`` and ``launch/steps.make_vfl_zoo_step``)
    call this so they cannot diverge."""
    dp = dict(zip(mesh.axis_names, mesh.devices.shape))[data_axis]
    if dp > 1:
        ex = ShardFoldedExchange(ex, data_axis)
    return PmeanVFLModel(model, data_axis), ex, dp


def make_sharded_train_fn(model: VFLModel, vfl: VFLConfig, n: int,
                          batch_size: int, algorithm: str = "asyrevel",
                          mesh=None, data_axis: str = "data"):
    """Build the jitted data-parallel scan: ``fn(state, keys, data) ->
    (state, losses)`` with the per-step batch sharded over ``mesh``'s
    ``data`` axis. Returned separately from ``train_sharded`` so repeat
    callers (throughput benches) reuse one compiled executable. ``n`` is
    the dataset's sample count (index-draw range)."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding.ctx import suspend_constraints
    from repro.sharding.rules import replicated_pspecs

    if mesh is None:
        mesh = jax.make_mesh((jax.device_count(),), (data_axis,))
    step_fn = asyrevel_step if algorithm == "asyrevel" else synrevel_step
    pmodel, ex, dp = shard_wrap(model, ZOExchange.from_config(vfl), mesh,
                                data_axis)
    assert batch_size % dp == 0, \
        f"batch_size={batch_size} must divide over {data_axis}={dp}"
    local_b = batch_size // dp

    def scan_fn(state, keys, data):
        # traced INSIDE shard_map: with_sharding_constraint is invalid in
        # manual-mesh bodies, so ambient activation constraints suspend
        with suspend_constraints():
            def body(state, k):
                # the GLOBAL index draw is replicated (same key on every
                # device); each shard then takes its own contiguous slice
                idx = jax.random.randint(k, (batch_size,), 0, n)
                r = jax.lax.axis_index(data_axis)
                idx = jax.lax.dynamic_slice_in_dim(
                    idx, r * local_b, local_b)
                batch = jax.tree.map(lambda a: a[idx], data)
                return step_fn(pmodel, vfl, state, batch, ex)

            return jax.lax.scan(body, state, keys)

    rep = replicated_pspecs

    def sharded(state, keys, data):
        return jax.shard_map(
            scan_fn, mesh=mesh,
            in_specs=(rep(state), P(), rep(data)),
            out_specs=(rep(state), P()),
            check_vma=False)(state, keys, data)

    return jax.jit(sharded)


def train_sharded(model: VFLModel, vfl: VFLConfig, data, key, steps: int,
                  batch_size: int, algorithm: str = "asyrevel", mesh=None,
                  data_axis: str = "data"):
    """Data-parallel ``train``: the per-step batch shards over ``mesh``'s
    ``data`` axis, the server loss is psum-reduced to the global batch
    mean, and party/server params stay replicated (the ZO update is a
    deterministic function of the replicated keys + the pmean'd scalars,
    so no parameter collective is ever needed).

    On a 1-device mesh this is bit-identical to ``train`` with the same
    seed: the batch indices, perturbation keys, and update order are
    byte-for-byte the same schedule, and pmean over a singleton axis is
    the identity. On dp devices the only numeric difference is the
    fp-reassociation of the batch mean (mean of dp shard-means).

    Lossy up-link codecs quantize per (message, shard): each device's
    slice of a party upload is its own wire tensor with its own absmax
    scale AND its own rounding key (ShardFoldedExchange folds the shard
    index in when dp > 1) — the per-MESSAGE granularity of the protocol,
    refined to the independent per-shard messages a data-parallel party
    would actually send.
    """
    n = jax.tree.leaves(data)[0].shape[0]
    fn = make_sharded_train_fn(model, vfl, n, batch_size, algorithm, mesh,
                               data_axis)
    state = init_state(model, vfl, key)
    keys = jax.random.split(jax.random.fold_in(key, 7), steps)
    return fn(state, keys, data)
