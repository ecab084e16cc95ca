"""ZOExchange — the ONE implementation of Algorithm 1's message round.

The paper's central systems claim is that nothing but function values ever
crosses the party/server boundary: party m uploads (c_m, c_hat_m), the
server replies (h, h_bar), and both sides form their updates from those
scalars plus purely local state. Before this module existed that round was
implemented four separate times (asyrevel_step, synrevel_step, the
threaded HostAsyncTrainer/_Server pair, and zo_sgd_step); this class owns
it once, so the privacy boundary is enforced — and instrumented — in one
place.

Mapping to Algorithm 1 (see also docs/exchange.md):

  line 4  (party m computes c, c_hat on private data)   perturb()
  line 5  (party m sends c, c_hat up)                   encode_up()/decode_up()
  line 8  (server returns h, h_bar down)                send_down()
  line 6  (two-point coefficient, Eqs. 14-15)           coefficient(),
                                                        party_gradient()
  line 7  (party update w_m)                            apply_block(),
                                                        apply_direction(),
                                                        apply_from_seed()
  lines 9-11 (server's own estimate + update, Eq. 17)   server_update()

Codec-aware transport: the up-link payload (the c function values — the
only non-scalar message in the protocol) goes through a pluggable
``Codec`` (f32 passthrough, bf16, or stochastic-rounded int8). Byte
counts are MEASURED from the encoded wire arrays (``wire_nbytes``), not
hand-derived; ``core/comms.py``'s analytic PRCO formulas are validated
against these counters in tests/test_exchange.py.

Differential privacy rides the same seam: with ``dp`` set (a
``configs.DPConfig`` with a resolved noise multiplier — see
``repro.dp``), every up-link payload is clipped-then-noised BEFORE the
codec runs, in both the measured ``encode_up`` path and the jit-traced
``roundtrip_up`` path, with noise keys derived from the same per-round
keys the stochastic codec uses. A defended in-memory host run and a
defended TCP run of one seed are therefore bit-identical (they execute
the same helpers with the same keys — pinned in tests/test_dp.py); the
scan trainer is seed-deterministic too but keys its uploads per STEP
(its own schedule), so it is not noise-identical to the host executors,
exactly as its undefended trajectory already differs from theirs.
``dp=None`` — or a disabled config (eps=inf) — is byte-for-byte the
undefended code path.

Inside jit/scan the per-round payload size is static, so jit paths use
``round_comms()`` (shape-derived, same arithmetic as the measured path);
the threaded host executor attaches a ``CommsMeter`` and accumulates the
real encoded-array sizes round by round.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import VFLConfig
from repro.core import zoo
from repro.core.comms import RoundComms
from repro.kernels import fused_round
from repro.utils.prng import fold_name

SCALAR_BYTES = 4          # every function value on the wire is one f32


def wire_nbytes(wire) -> int:
    """Measured payload size: total bytes of the encoded wire arrays.
    Reads ``.nbytes`` off the arrays themselves (jax and numpy both carry
    it) so metering never forces a device->host copy on the hot path."""
    return int(sum(
        leaf.nbytes if hasattr(leaf, "nbytes") else np.asarray(leaf).nbytes
        for leaf in jax.tree.leaves(wire)))


# ----------------------------------------------------------------- codecs --

class Codec:
    """Encodes the party->server payload (the c function-value vectors).

    ``encode`` may take a PRNG key (used by stochastic rounding); ``decode``
    returns the float32 values the server actually consumes. ``nbytes`` is
    the wire size computed from the UNencoded value's shape — it must agree
    with ``wire_nbytes(encode(c))``, and tests assert that it does.
    """

    name = "abstract"

    def encode(self, c, key=None):
        raise NotImplementedError

    def decode(self, wire):
        raise NotImplementedError

    def nbytes(self, c) -> int:
        raise NotImplementedError

    def roundtrip(self, c, key=None):
        return self.decode(self.encode(c, key))


class F32Codec(Codec):
    """Lossless passthrough — the paper's own wire format."""

    name = "f32"

    def encode(self, c, key=None):
        return jnp.asarray(c, jnp.float32)

    def decode(self, wire):
        return wire

    def nbytes(self, c) -> int:
        return int(np.prod(np.shape(c))) * 4


class BF16Codec(Codec):
    """Halves up-link bytes; ~3 decimal digits of the function values."""

    name = "bf16"

    def encode(self, c, key=None):
        return jnp.asarray(c).astype(jnp.bfloat16)

    def decode(self, wire):
        return wire.astype(jnp.float32)

    def nbytes(self, c) -> int:
        return int(np.prod(np.shape(c))) * 2


@jax.jit
def _int8_decode(q, scale):
    # one dispatch for the server-side dequant; the int8->f32 convert is
    # exact and the multiply has no fusion partner, so this is bitwise the
    # eager two-op chain
    return q.astype(jnp.float32) * scale


class Int8StochasticCodec(Codec):
    """Per-tensor absmax scale + stochastic rounding to int8.

    E[decode(encode(c))] = c (the rounding noise is zero-mean), so the
    two-point coefficient stays an unbiased function-value difference —
    the DPZV-style compression of exactly this channel. Wire = int8 values
    + one f32 scale.
    """

    name = "int8"

    def encode(self, c, key=None):
        c = jnp.asarray(c, jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(c)), 1e-12) / 127.0
        x = c / scale
        if key is not None:
            x = jnp.floor(x + jax.random.uniform(key, c.shape))
        else:
            x = jnp.round(x)
        q = jnp.clip(x, -127, 127).astype(jnp.int8)
        return q, scale

    def decode(self, wire):
        q, scale = wire
        if isinstance(q, np.ndarray):
            # host wires (threaded/TCP runtimes ship numpy): dequantize on
            # the host — the int8->f32 convert is exact and numpy's f32
            # multiply is the same IEEE-754 single-rounding op XLA emits,
            # so this is bitwise the device path without the device_put /
            # dispatch / sync round-trip per payload
            return q.astype(np.float32) * np.float32(np.asarray(scale))
        return _int8_decode(q, scale)

    def nbytes(self, c) -> int:
        return int(np.prod(np.shape(c))) + 4          # values + scale


CODECS = {c.name: c for c in (F32Codec(), BF16Codec(), Int8StochasticCodec())}


def get_codec(codec) -> Codec:
    if isinstance(codec, Codec):
        return codec
    try:
        return CODECS[codec]
    except KeyError:
        raise ValueError(
            f"unknown codec {codec!r}; have {sorted(CODECS)}") from None


# ------------------------------------------------------------------ meter --

@dataclass
class CommsMeter:
    """Measured transport counters, accumulated round by round."""

    up_bytes: int = 0
    down_bytes: int = 0
    rounds: int = 0

    def add_up(self, n: int):
        self.up_bytes += int(n)

    def add_down(self, n: int):
        self.down_bytes += int(n)

    def add_round(self):
        self.rounds += 1


# --------------------------------------------------------------- exchange --

class ZOExchange:
    """Owns the full two-point round of Algorithm 1 (see module docstring).

    Stateless apart from the optional ``meter`` — safe to construct inside
    a jitted trace (jit paths pass ``meter=None``; traced code must not
    mutate Python counters per step).
    """

    def __init__(self, mu: float, direction: str = "gaussian",
                 lam: float = 0.0, num_directions: int = 1, codec="f32",
                 meter: CommsMeter | None = None, dp=None,
                 fused: bool = False):
        self.mu = mu
        self.direction = direction
        self.lam = lam
        self.num_directions = num_directions
        self.codec = get_codec(codec)
        self.meter = meter
        # fused=True routes every release (defend, encode_up,
        # roundtrip_up) and apply_direction through the single-dispatch
        # kernels/fused_round fast path; the unfused code below stays the
        # bit-parity oracle (tests/test_kernels.py pins them equal). The
        # direction's draw and its seed-regenerated apply have one
        # implementation, core/zoo, whatever ``fused`` says.
        self.fused = bool(fused)
        # a disabled DPConfig (eps=inf) normalizes to None so the
        # defended-off exchange IS the undefended one (same hash, same
        # code path — the eps=inf bit-identity claim by construction)
        self.dp = dp if (dp is not None and dp.enabled) else None
        if self.dp is not None and not self.dp.resolved:
            raise ValueError(
                "DPConfig has a target epsilon but no noise_multiplier — "
                "calibrate it first via repro.dp.accountant.resolve_dp(dp, "
                "rounds=...) (the launcher/harness does this where the "
                "round budget is known)")

    @classmethod
    def from_config(cls, vfl: VFLConfig,
                    meter: CommsMeter | None = None) -> "ZOExchange":
        return cls(mu=vfl.mu, direction=vfl.direction, lam=vfl.lam,
                   num_directions=vfl.num_directions,
                   codec=getattr(vfl, "codec", "f32"), meter=meter,
                   dp=getattr(vfl, "dp", None),
                   fused=getattr(vfl, "fused", False))

    # ---- wire: party -> server (Algorithm 1 line 5) ----------------------
    def _codec_key(self, key):
        """Hook: the rounding key a stochastic codec actually uses.
        Identity here; the sharded trainer's subclass folds the device's
        data-axis index in so per-shard messages draw independent
        rounding noise (core/asyrevel.ShardFoldedExchange)."""
        return key

    def _dp_key(self, key):
        """The DP-noise key of one release: independent of the codec
        rounding stream (named fold), then the same shard fold — a
        data-parallel party's per-shard slices are separate releases
        and must draw independent noise."""
        if key is None:
            raise ValueError(
                "a DP-defended exchange needs the round key on every "
                "up-link (the noise draw is keyed like codec rounding)")
        return self._codec_key(fold_name(key, "dp_noise"))

    def defend(self, c, key):
        """Clip-then-noise one up-link payload (identity when dp=None).
        ``key`` is the release's ROUND key — the dp-noise subkey derives
        inside, so callers pass the same key they pass encode_up."""
        if self.dp is None:
            return c
        if self.fused:
            return fused_round.defend_fused(self, c, key)
        from repro.dp.mechanisms import defend_payload
        return defend_payload(c, self._dp_key(key), self.dp)

    @jax.named_scope("exchange_up")
    def encode_up(self, c, key=None):
        """Party side: function values -> wire payload (+ measured bytes).
        The DP defense (clip-then-noise, repro/dp) applies HERE, before
        the codec — the one seam every executor's up-link crosses. With
        ``fused`` the whole clip -> noise -> encode chain runs as ONE
        dispatch (kernels/fused_round), bit-identical to this path."""
        if self.fused:
            wire = fused_round.encode_up_fused(self, c, key)
        else:
            wire = self.codec.encode(self.defend(c, key),
                                     self._codec_key(key))
        if self.meter is not None:
            self.meter.add_up(wire_nbytes(wire))
        return wire

    def decode_up(self, wire):
        """Server side: wire payload -> the f32 values F_0 consumes."""
        return self.codec.decode(wire)

    @jax.named_scope("exchange_up")
    def roundtrip_up(self, c, key=None):
        """What the server sees after the up-link (identity for f32 with
        dp off) — the jit-traced twin of encode_up + decode_up."""
        if self.fused:
            return fused_round.roundtrip_up_fused(self, c, key)
        return self.codec.roundtrip(self.defend(c, key),
                                    self._codec_key(key))

    # ---- wire: server -> party (Algorithm 1 line 8) ----------------------
    def send_down(self, *fvals):
        """The reply is scalar function values only — h, h_bar (and one
        h_bar per extra direction). Metered per ROUND, not per sample: the
        server returns batch-mean losses."""
        if self.meter is not None:
            self.meter.add_down(len(fvals) * SCALAR_BYTES)
        return fvals if len(fvals) > 1 else fvals[0]

    # ---- estimator math (Eqs. 14-15) -------------------------------------
    @jax.named_scope("zo_perturb")
    def perturb(self, w, key):
        """w + mu * u. Returns (perturbed_tree, u_tree)."""
        return zoo.perturb(w, key, self.mu, self.direction)

    def coefficient(self, f_plus, f_base):
        """[f(w + mu u) - f(w)] / mu — the only derived scalar a party
        ever forms from remote data."""
        return zoo.zo_coefficient(f_plus, f_base, self.mu)

    def party_gradient(self, w_m, key, f_base, f_of):
        """The party-side estimate, averaged over K directions.

        ``f_of(w_pert, k_dir)`` evaluates the full objective at the
        perturbed block — it hides one (c_hat up, h_bar down) round trip
        plus the party's private regularizer. ``k_dir`` is that
        direction's OWN subkey: a stochastic up-link codec must fold it
        into its rounding key so the K uploads carry independent rounding
        noise (shared noise would break the K-direction variance
        reduction). ``f_base`` is the unperturbed value (h + lam *
        g(w_m)). Returns the ZO gradient tree.

        K > 1 is evaluated as ONE batched round, not K sequential round
        trips: all K perturbed blocks are stacked and ``f_of`` is vmapped
        over the direction axis, so the K (c_hat up, h_bar down)
        exchanges fuse into a single multi-direction dispatch.
        """
        K = self.num_directions
        if K == 1:
            w_p, u = self.perturb(w_m, key)
            coeff = self.coefficient(f_of(w_p, key), f_base)
            with jax.named_scope("zo_update"):
                return zoo.zo_gradient(u, coeff)
        keys = jax.random.split(key, K)
        w_ps, us = jax.vmap(lambda k: self.perturb(w_m, k))(keys)
        coeffs = jax.vmap(
            lambda f: self.coefficient(f, f_base))(jax.vmap(f_of)(w_ps, keys))
        with jax.named_scope("zo_update"):
            return self.mean_direction(coeffs, us)

    @staticmethod
    def mean_direction(coeffs, us):
        """mean_k coeff_k * u_k: how K directions combine into one
        estimate. ``coeffs`` is (K,); every leaf of ``us`` stacks the K
        directions on its leading axis."""
        K = coeffs.shape[0]
        return jax.tree.map(
            lambda u: jnp.mean(
                coeffs.reshape((K,) + (1,) * (u.ndim - 1)) * u, axis=0),
            us)

    # ---- update apply (Algorithm 1 line 7 / Eq. 15) ----------------------
    @jax.named_scope("zo_update")
    def apply_block(self, stacked, m, g, lr: float):
        """In-place-style block-coordinate update of party m inside the
        stacked (q, ...) parameter tree."""
        return jax.tree.map(
            lambda a, gg: a.at[m].add((-lr * gg).astype(a.dtype)),
            stacked, g)

    @jax.named_scope("zo_update")
    def apply_direction(self, w, u, coeff, lr: float):
        """Dense update from a materialized direction: w - lr * coeff * u."""
        if self.fused:
            return fused_round.apply_direction_fused(w, u, coeff, lr)
        return jax.tree.map(
            lambda a, d: (a - lr * coeff * d).astype(a.dtype), w, u)

    @jax.named_scope("zo_update")
    def apply_from_seed(self, w, key, coeff, lr: float):
        """Seed-replay update: regenerate u from ``key``; never store it."""
        return zoo.apply_zo_update(w, key, self.direction, coeff, lr)

    # ---- server side (Algorithm 1 lines 9-11 / Eq. 17) -------------------
    def server_update(self, w0, key, f_base, f_of, lr: float):
        """The server's own two-point estimate and update. ``f_of(w0p)``
        re-evaluates F_0 on the SAME received c table — no extra up-link."""
        w0p, u0 = self.perturb(w0, key)
        coeff = self.coefficient(f_of(w0p), f_base)
        with jax.named_scope("zo_update"):
            g0 = zoo.zo_gradient(u0, coeff)
            return jax.tree.map(
                lambda a, g: (a - lr * g).astype(a.dtype), w0, g0)

    # ---- accounting -------------------------------------------------------
    def round_comms(self, c) -> RoundComms:
        """Measured per-round transport for one party round with payload
        shaped like ``c``: the base c plus one c_hat per direction go up;
        h plus one h_bar per direction come down. Shape-derived, so usable
        from inside jit-compiled paths where a Python meter cannot run."""
        K = self.num_directions
        return RoundComms((1 + K) * self.codec.nbytes(c),
                          (1 + K) * SCALAR_BYTES)

    # Instances hash by semantics so they can ride in jit static args.
    def _hash_key(self):
        return (self.mu, self.direction, self.lam, self.num_directions,
                self.codec.name, self.dp, self.fused)

    def __hash__(self):
        return hash(self._hash_key())

    def __eq__(self, other):
        return (type(other) is type(self)
                and self._hash_key() == other._hash_key())
