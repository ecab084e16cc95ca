"""Dual-evaluation matmul kernel — the AsyREVEL hot spot.

Every AsyREVEL step evaluates the party tower TWICE: F(w; x) and
F(w + mu*u; x) (Eq. 15's two function values). Done naively that is two
matmuls streaming X and W from HBM twice. This kernel produces BOTH outputs
in one pass: each (bk, bn) W-tile and (bm, bk) X-tile is loaded into VMEM
once, the perturbation tile U is applied in-register, and two fp32
accumulators run in VMEM scratch.

HBM traffic:  naive 2x(X + W) reads -> fused 1x(X + W + U); with U
regenerated on-chip from a PRNG seed on real TPU (see zo_update) the U read
disappears too. MXU alignment: tiles default to (128, 512, 128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import interpret_mode


def _kernel(x_ref, w_ref, u_ref, y0_ref, y1_ref, acc0_ref, acc1_ref, *,
            mu: float, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc0_ref[...] = jnp.zeros_like(acc0_ref)
        acc1_ref[...] = jnp.zeros_like(acc1_ref)

    # f32 operands + f32 accumulators: bf16 inputs would otherwise lose
    # the mu*u perturbation (|mu*u| << |w| vs bf16's ~8-bit mantissa) and
    # round per-tile partial products. HIGHEST keeps the MXU from
    # truncating the f32 operands to one bf16 pass on the chip.
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    acc0_ref[...] += jnp.dot(x, w, precision=hi,
                             preferred_element_type=jnp.float32)
    acc1_ref[...] += jnp.dot(x, w + mu * u, precision=hi,
                             preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        y0_ref[...] = acc0_ref[...].astype(y0_ref.dtype)
        y1_ref[...] = acc1_ref[...].astype(y1_ref.dtype)


@functools.partial(jax.jit, static_argnames=("mu", "bm", "bn", "bk",
                                             "interpret"))
def dual_matmul_pallas(x, w, u, *, mu: float, bm: int = 128, bn: int = 128,
                       bk: int = 512, interpret: bool | None = None):
    """x: (M,K); w,u: (K,N). Returns (x@w, x@(w+mu*u)), fp32-accumulated."""
    M, K = x.shape
    _, N = w.shape
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, \
        (M, N, K, bm, bn, bk)
    n_k = K // bk
    grid = (M // bm, N // bn, n_k)
    out = jax.ShapeDtypeStruct((M, N), x.dtype)
    return pl.pallas_call(
        functools.partial(_kernel, mu=mu, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        ],
        out_shape=[out, out],
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, bn), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(x, w, u)
