"""PRNG plumbing: named key folding so every module gets a stable stream."""
from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp


def fold_name(key, name: str):
    """Deterministically fold a string into a PRNG key."""
    h = int.from_bytes(hashlib.sha256(name.encode()).digest()[:4], "little")
    return jax.random.fold_in(key, h)


def key_iter(key):
    """Infinite iterator of fresh subkeys."""
    while True:
        key, sub = jax.random.split(key)
        yield sub


def rademacher_from_bits(bits, dtype=jnp.float32):
    """u_i = +1 where the low bit of ``bits`` is set, else -1: the
    Rademacher law from a raw uint32 stream."""
    return jnp.where((bits & 1) == 1, 1.0, -1.0).astype(dtype)


def sample_direction(key, shape, dist: str, dtype=jnp.float32):
    """Random direction u for the two-point estimator.

    dist='gaussian'  : u ~ N(0, I)           (AsyREVEL-Gau)
    dist='uniform'   : u ~ Unif(S^{d-1})·√d  (AsyREVEL-Uni; the √d keeps E||u||²=d,
                       matching the Gaussian normalization so Eq.(15)'s d_m/μ_m
                       prefactor is shared — the paper's two theorems differ only
                       in the d_* constant.)
    dist='rademacher': u_i = ±1 (E[uu^T] = I — a valid two-point law, beyond
                       paper). Signs derive from the low bit of the PRNG
                       stream (``rademacher_from_bits``), bit-compatible
                       with the Pallas zo_update kernel's tree wrappers
                       (kernels/fused_round.zo_apply and perturb).
    """
    if dist == "gaussian":
        return jax.random.normal(key, shape, dtype)
    elif dist == "uniform":
        g = jax.random.normal(key, shape, jnp.float32)
        d = g.size
        u = g / (jnp.linalg.norm(g.reshape(-1)) + 1e-12) * jnp.sqrt(float(d))
        return u.astype(dtype)
    elif dist == "rademacher":
        return rademacher_from_bits(jax.random.bits(key, shape, jnp.uint32),
                                    dtype)
    raise ValueError(f"unknown direction distribution: {dist}")
