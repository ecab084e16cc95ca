"""Jit'd public wrappers around the Pallas kernels.

``interpret=None`` lets the platform decide (kernels/platform.py): the
kernels compile on a TPU and interpret elsewhere. The wrappers handle
layout plumbing — GQA group expansion for attention — so callers stay
shape-simple. The ZO update kernel's tree wrappers are
kernels/fused_round.zo_apply and perturb.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.dual_matmul import dual_matmul_pallas
from repro.kernels.flash_attention import flash_attention_pallas


def dual_matmul(x, w, u, mu: float, *, interpret: bool | None = None,
                **tiles):
    """(x@w, x@(w+mu*u)) with one pass over x/w. x: (M,K), w/u: (K,N)."""
    return dual_matmul_pallas(x, w, u, mu=mu, interpret=interpret, **tiles)


def flash_attention(q, k, v, *, causal: bool = True,
                    interpret: bool | None = None, **tiles):
    """q: (B,S,H,hd); k,v: (B,S,KV,hd) GQA. Returns (B,S,H,hd)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    kx = jnp.repeat(k, G, axis=2) if G > 1 else k
    vx = jnp.repeat(v, G, axis=2) if G > 1 else v
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = kx.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vf = vx.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    o = flash_attention_pallas(qf, kf, vf, causal=causal,
                               interpret=interpret, **tiles)
    return o.reshape(B, H, S, hd).transpose(0, 2, 1, 3)

