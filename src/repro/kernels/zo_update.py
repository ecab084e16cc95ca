"""Fused seed-replay ZO update kernel (beyond-paper, MeZO-style).

AsyREVEL's update is w <- w - lr * coeff * u where coeff is ONE scalar per
step and u is the random direction. Materializing u doubles parameter
traffic. With seed-replay + Rademacher directions (u_i = +-1, E[uu^T] = I —
a valid two-point-estimator law), u derives from one random BIT per
element: the kernel reads w and the packed bits, forms u in-register, and
writes the update — no f32 u ever exists in HBM. The bits are a uint32
operand on every platform, so the kernel (compiled or interpreted) replays
exactly the stream the unfused oracle draws.

coeff arrives in SMEM as a (1,1) scalar so the same compiled kernel serves
every step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import interpret_mode


@functools.lru_cache(maxsize=None)
def runtime_zero():
    """A device-resident uint32 zero, passed INTO jitted code as an
    argument so the compiler must treat it as a runtime value. Forced
    eager — a bare jnp.zeros would return (and cache!) a tracer when the
    first call happens under an active trace."""
    with jax.ensure_compile_time_eval():
        return jnp.zeros((), jnp.uint32) + np.uint32(0)


def rounded_product(a, b, z):   # zvlint: bit-exact
    """a * b forced to round as its own f32 op.

    XLA's codegen contracts a multiply feeding an add/sub into one fused
    multiply-add, which lands 1 ulp off the eagerly-dispatched unfused
    oracle (eager ops compile one at a time, so they can never contract).
    Every HLO-level blocker — optimization_barrier, bitcast round-trips,
    reduce_precision — is simplified away before that happens; what
    actually pins the rounding point is routing the product's bits
    through an XOR with ``z``, a RUNTIME zero the compiler cannot fold.
    ``z`` must therefore be a traced value (``runtime_zero()`` passed as
    a jit argument), never a Python or in-trace constant.
    """
    p = a * b
    return jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(p, jnp.uint32) ^ z, jnp.float32)


def rounded_quotient(a, b, z):   # zvlint: bit-exact
    """a / b forced to compile as a true division.

    When ``b`` is a compile-time constant, XLA's algebraic simplifier
    rewrites the divide into a multiply by 1/b — 1 ulp off true division
    for some operands, so a jitted chain drifts from the eager oracle
    (which compiles the division alone and never rewrites it). XORing
    the divisor's bits with the runtime zero ``z`` makes it a runtime
    value the simplifier must divide by."""
    bz = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(jnp.float32(b), jnp.uint32) ^ z,
        jnp.float32)
    return a / bz


def _kernel(scale_ref, z_ref, w_ref, bits_ref, out_ref):   # zvlint: bit-exact
    # u = +1 where bit set else -1
    u = jnp.where((bits_ref[...] & 1) == 1, 1.0, -1.0).astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    step = rounded_product(scale_ref[0, 0], u, z_ref[0])
    out_ref[...] = (w - step).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _zo_update_jit(w, bits, scale, z, *, block, interpret):
    (N,) = w.shape
    block = min(block, max(N, 1))
    pad = (-N) % block
    if pad:
        w = jnp.pad(w, (0, pad))
        bits = jnp.pad(bits, (0, pad))
    scale2d = scale.reshape(1, 1).astype(jnp.float32)
    out = pl.pallas_call(
        _kernel,
        grid=((N + pad) // block,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((N + pad,), w.dtype),
        interpret=interpret_mode(interpret),
    )(scale2d, z.reshape(1), w, bits)
    return out[:N] if pad else out


def zo_update_pallas(w, bits, scale, *, block: int = 1024,
                     interpret: bool | None = None):
    """w: (N,) params; bits: (N,) uint32; scale: () f32 = lr*coeff.

    Returns w - scale * rademacher(bits), bit-identical to the eager
    unfused chain for f32 ``w`` (the scale*u product rounds on its own —
    see ``rounded_product``). Arbitrary N: the input pads to a block
    multiple and the tail lanes are sliced off the output (the kernel's
    padded lanes compute garbage that never escapes), so the grid stays
    dense without any N % block restriction.
    """
    return _zo_update_jit(w, bits, scale, runtime_zero(), block=block,
                          interpret=interpret)
