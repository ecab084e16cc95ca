"""Clip-then-noise mechanisms at the ``ZOExchange.encode_up`` seam.

What is released, and why the seam is the right place
-----------------------------------------------------

Every party->server crossing in ZOO-VFL is a vector of per-sample
function values c_{i,m} = F_m(w_m; x_{i,m}) (the base c plus one c_hat
per direction; see core/wire.py). Sample i's private features at party m
influence exactly ONE entry of each of that party's releases, so the
mechanism is the textbook clipped-scalar release:

  1. clip:   every entry is clamped to [-C, C]  (C = ``DPConfig.clip``),
             so one sample's contribution has L2 (and L1) sensitivity C
             under add/remove adjacency;
  2. noise:  add mechanism noise of scale sigma * C per entry
             (``sigma = DPConfig.noise_multiplier``):
             gaussian -> N(0, (sigma*C)^2); laplace -> Lap(b = sigma*C).

The defended (still-float32) values then enter the configured up-link
codec (f32/bf16/int8) unchanged — DP composes with compression because
the noise is added BEFORE quantization, on the cleartext the codec would
have shipped. Post-processing (codec, server math, attacks) cannot spend
privacy budget, so the accountant only counts encode_up releases.

Determinism
-----------

The noise key derives from the SAME per-round key the stochastic codec
uses (``fold_name(key, "dp_noise")``, then the exchange's shard fold for
data-parallel bodies), which itself derives from the trainer seed. A
memory run and a TCP run of the same seed therefore draw bit-identical
noise — the runtime's bit-parity acceptance extends to defended runs.

THREAT-MODEL CAVEAT: seed-derived noise is a property of this
reproduction HARNESS (every process rebuilds the problem from one shared
spec so transports can be compared bit-for-bit), and it means an
adversary who holds the run seed — e.g. the simulated curious server,
which receives the same spec — could regenerate and subtract the noise.
The (eps, delta) guarantee is against adversaries who observe the WIRE,
not the seed. A real deployment must draw each party's noise key from
party-private entropy (only the party-side ``encode_up`` call changes;
nothing downstream inspects the key), trading away cross-transport
bit-reproducibility for actual noise secrecy — see docs/dp.md.

``DPConfig.epsilon = inf`` (or ``dp=None``) disables everything: the
exchange normalizes a disabled config away, so the defended-off path is
byte-for-byte the undefended code path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import DPConfig
from repro.core.exchange import ZOExchange


def noise_scale(dp: DPConfig) -> float:
    """Absolute per-entry noise scale: sigma * clip (std for gaussian,
    the Laplace ``b`` for laplace)."""
    if dp.noise_multiplier is None:
        raise ValueError(
            "DPConfig.noise_multiplier is unresolved — calibrate it from "
            "the target epsilon with repro.dp.accountant.resolve_dp(dp, "
            "rounds=...) before running")
    return float(dp.noise_multiplier) * float(dp.clip)


def defend_payload(c, key, dp: DPConfig):
    """Clip-then-noise one release. ``key`` must be that release's own
    subkey (each of a round's (1+K) uploads draws independent noise).
    jit-safe; returns float32 values ready for the up-link codec."""
    if not dp.enabled:
        return c
    c = jnp.clip(jnp.asarray(c, jnp.float32), -dp.clip, dp.clip)
    scale = noise_scale(dp)
    if scale == 0.0:
        return c                      # clip-only (sigma = 0): no noise draw
    if dp.mechanism == "gaussian":
        return c + scale * jax.random.normal(key, jnp.shape(c), jnp.float32)
    return c + scale * jax.random.laplace(key, jnp.shape(c), jnp.float32)


class DPExchange(ZOExchange):
    """The defended exchange: a ZOExchange whose ``dp`` config is
    mandatory. ``ZOExchange`` itself carries the (optional) dp hook so
    subsystem composition — ``ShardFoldedExchange``, ``from_config`` —
    inherits the defense for free; this subclass is the explicit
    entry point for constructing a defended seam directly:

        ex = DPExchange(resolve_dp(DPConfig(epsilon=8, clip=1.0),
                                   rounds=T), mu=1e-3, codec="int8")
    """

    def __init__(self, dp: DPConfig, **kw):
        if dp is None or not dp.enabled:
            raise ValueError(
                "DPExchange requires an ENABLED DPConfig (finite epsilon "
                "or an explicit noise_multiplier, plus a clip bound); use "
                "plain ZOExchange for the undefended path")
        super().__init__(dp=dp, **kw)

    @classmethod
    def wrap(cls, base: ZOExchange, dp: DPConfig) -> "DPExchange":
        """A defended copy of an existing exchange's semantics."""
        return cls(dp, mu=base.mu, direction=base.direction, lam=base.lam,
                   num_directions=base.num_directions, codec=base.codec,
                   meter=base.meter, fused=base.fused)
