"""Fused defended-round hot path: perturb / clip / DP-noise / quantize
as single passes, bit-identical to the unfused seam.

One defended up-link (core/exchange.py ``encode_up``) is a chain of
separately materialized steps — ``jnp.clip``, a mechanism noise draw,
the add, then the codec's scale/round/cast — each an HBM round-trip on
TPU and a separate eager dispatch on the CPU hosts. This module fuses
the whole chain. Every op ships two interchangeable implementations:

  impl='xla'     ONE jitted elementwise chain — the production fast path
                 on CPU executors (a single dispatch replaces the
                 unfused seam's ~8 per-op eager dispatches);
  impl='pallas'  the TPU kernel (compiled on a TPU, interpreted on other
                 platforms — kernels/platform.py), reading
                 parameters/payload blocks once and writing the
                 encoded result once — no intermediate u, clipped-c, or
                 noised-c array ever lands in HBM. int8 is two passes
                 (masked block absmax, then quantize), both recomputing
                 the defended values in-register.

Bit parity, and why it is possible
----------------------------------

The unfused oracle draws noise with ``jax.random.normal/laplace`` and
rounding with ``jax.random.uniform``. All three consume exactly the raw
stream ``jax.random.bits(key, shape, uint32)`` and post-process it with
a short, fixed float chain (mantissa-fill to [0,1), affine to the open
interval, then erf_inv / log1p). The helpers below replicate those
chains bit-for-bit from the bits (pinned in tests/test_kernels.py), so
both implementations take the SAME uint32 operands the MeZO-style
``zo_update`` kernel already uses, on every platform. Under the existing
per-round key derivation (``_dp_key`` / ``_codec_key``) a fused exchange
is therefore bitwise identical to the unfused one, and the TCP-vs-memory
parity pins survive with ``fused=True`` unchanged.

The perturb/apply side reuses kernels/zo_update.py: ``w + mu*u`` is the
same kernel as ``w - scale*u`` at ``scale = -mu`` (IEEE subtraction of
a negated product is exact), and the update's ``scale = lr*coeff``
matches the oracle's ``w - (lr*coeff)*u`` evaluation order, so f32
parameter parity is bitwise. See docs/kernels.md.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import interpret_mode
from repro.kernels.zo_update import (rounded_product, rounded_quotient,
                                     runtime_zero, zo_update_pallas)

# every payload pads to whole 1024-lane blocks: Mosaic lays a rank-1 int8
# or bf16 block out in packed sublanes and refuses a block that does not
# fill them, so small payloads may not take a block of their own size
_BLOCK = 1024
_F32_ONE = np.uint32(0x3F800000)
# the open-interval lower bounds jax.random uses before erf_inv / log1p
_NORMAL_LO = np.nextafter(np.float32(-1.0), np.float32(0.0))
_LAPLACE_LO = np.float32(-1.0 + np.finfo(np.float32).epsneg)
_SQRT2 = np.float32(np.sqrt(2.0))


# -------------------------------------------- bits -> distribution chains --
# Each helper is bitwise identical to its jax.random counterpart when fed
# bits = jax.random.bits(key, shape, uint32) — the same stream those
# samplers consume internally. Pure elementwise lax, so the same code
# runs inside a Pallas kernel body and in a jitted XLA chain.

def uniform_from_bits(bits):   # zvlint: bit-exact
    """== jax.random.uniform(key, shape) on the key that produced bits:
    9-bit shift fills the f32 mantissa, bitcast to [1,2), subtract 1."""
    f = jax.lax.bitcast_convert_type(
        jnp.bitwise_or(jnp.right_shift(bits, np.uint32(9)), _F32_ONE),
        jnp.float32)
    return f - np.float32(1.0)


def _open_interval(u01, lo, z=None):   # zvlint: bit-exact
    """jax.random's uniform(lo, 1) remap: affine then clamp at lo.

    In a large fused graph XLA occasionally contracts the ``u01 * span +
    lo`` pair into an FMA (data-dependently 1 ulp off the oracle, whose
    own small jit never contracts it) — pass ``z`` (a runtime zero) from
    any jitted caller to pin the product's rounding."""
    span = np.float32(1.0) - lo
    if z is None:
        # zvlint: disable=kernel-float-safety — the z=None branch is for
        # EAGER callers only (ops compile one at a time, no contraction)
        return jax.lax.max(lo, u01 * span + lo)
    return jax.lax.max(lo, rounded_product(u01, span, z) + lo)


def normal_from_bits(bits, z=None):   # zvlint: bit-exact
    """== jax.random.normal: sqrt(2) * erf_inv(uniform(nextafter(-1,0), 1)).

    The oracle materializes this product (jax.random.normal is its own
    jit), so when the fused chain multiplies the result by a further
    constant, XLA's simplifier would merge sqrt(2) into it and re-round.
    Pass ``z`` (a runtime zero) whenever the caller is jitted.
    """
    u = _open_interval(uniform_from_bits(bits), _NORMAL_LO, z)
    r = jax.lax.erf_inv(u)
    return _SQRT2 * r if z is None else rounded_product(_SQRT2, r, z)


def laplace_from_bits(bits, z=None):   # zvlint: bit-exact
    """== jax.random.laplace: sign(u) * log1p(-|u|), u ~ uniform(-1+eps, 1).
    No constant factor on the result, but the interval remap still needs
    the ``z`` contraction guard (see _open_interval)."""
    u = _open_interval(uniform_from_bits(bits), _LAPLACE_LO, z)
    return jax.lax.mul(jax.lax.sign(u),
                       jax.lax.log1p(jax.lax.neg(jax.lax.abs(u))))


def rademacher_from_bits(bits):
    """== utils/prng.sample_direction(dist='rademacher'): the low bit."""
    return jnp.where((bits & 1) == 1, 1.0, -1.0).astype(jnp.float32)


_NOISE = {"gaussian": normal_from_bits, "laplace": laplace_from_bits}


# ------------------------------------------------- shared defended math ----

def _defend_math(c, dp_bits, dp, z):   # zvlint: bit-exact
    """Clip-then-noise from raw bits; the fused twin of
    dp/mechanisms.defend_payload. ``dp_bits is None`` covers both dp-off
    and the sigma=0 clip-only case (the oracle skips the draw there).
    ``z`` is the runtime zero that keeps the scale*noise product from
    contracting with the add (see zo_update.rounded_product)."""
    c = jnp.asarray(c, jnp.float32)
    if dp is None:
        return c
    c = jnp.clip(c, -dp.clip, dp.clip)
    if dp_bits is None:
        return c
    scale = np.float32(float(dp.noise_multiplier) * float(dp.clip))
    return c + rounded_product(scale, _NOISE[dp.mechanism](dp_bits, z), z)


def _encode_math(d, rnd_bits, codec: str, z=None):   # zvlint: bit-exact
    """The codec stage on already-defended f32 values; the fused twin of
    the core/exchange.py codec ``encode`` methods. ``z`` guards the
    /127.0 against the reciprocal-multiply rewrite (rounded_quotient)."""
    if codec == "f32":
        return d
    if codec == "bf16":
        return d.astype(jnp.bfloat16)
    if codec != "int8":
        raise ValueError(f"no fused encode for codec {codec!r}")
    amax = jnp.maximum(jnp.max(jnp.abs(d)), 1e-12)
    # zvlint: disable=kernel-float-safety — the z=None branch is for
    # EAGER callers only (no simplifier pass rewrites an eager divide)
    scale = (amax / 127.0 if z is None
             else rounded_quotient(amax, 127.0, z))
    x = d / scale
    if rnd_bits is not None:
        x = jnp.floor(x + uniform_from_bits(rnd_bits))
    else:
        x = jnp.round(x)
    return jnp.clip(x, -127, 127).astype(jnp.int8), scale


# ------------------------------------------------------- pallas kernels ----
# SMEM scalar layout (1, 3): [clip, noise_scale, quant_scale]. Static
# flags select the stages the kernel body actually emits; unused operands
# are traced away. Block absmax masks the pad lanes with a global-index
# iota (|defended| >= 0, so masked-to-0 lanes never win the max) and
# writes the block's maximum across one lane-aligned (1, 128) row of a
# (grid, 1, 128) output: Mosaic accepts no rank-1 output block of size 1.

def _make_defend_kernel(*, mechanism, has_dp, has_noise, stage, codec,
                        has_rnd, block, n):
    def kernel(sm_ref, z_ref, c_ref, dpb_ref, rnb_ref, o_ref):   # zvlint: bit-exact
        c = c_ref[...].astype(jnp.float32)
        if has_dp:
            c = jnp.clip(c, -sm_ref[0, 0], sm_ref[0, 0])
            if has_noise:
                z = z_ref[0]
                c = c + rounded_product(
                    sm_ref[0, 1], _NOISE[mechanism](dpb_ref[...], z), z)
        if stage == "absmax":
            # zvlint: disable=kernel-float-safety — int32 lane indexing;
            # integer FMA contraction is exact, no rounding to drift
            lane = (jax.lax.broadcasted_iota(jnp.int32, (block,), 0)
                    + pl.program_id(0) * block)
            o_ref[...] = jnp.broadcast_to(
                jnp.max(jnp.where(lane < n, jnp.abs(c), 0.0)), o_ref.shape)
        elif stage == "quant":
            x = c / sm_ref[0, 2]
            if has_rnd:
                x = jnp.floor(x + uniform_from_bits(rnb_ref[...]))
            else:
                x = jnp.round(x)
            o_ref[...] = jnp.clip(x, -127, 127).astype(jnp.int8)
        else:                                   # f32 / bf16 cast-out
            o_ref[...] = c.astype(o_ref.dtype)
    return kernel


def _defend_call(kernel, sm, z, flat, dpb, rnb, out_shape, out_dtype, block,
                 grid, interpret, out_spec=None):
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=out_spec or pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct(out_shape, out_dtype),
        interpret=interpret_mode(interpret),
    )(sm, z.reshape(1), flat, dpb, rnb)


def _pad1d(x, pad):
    return jnp.pad(x, (0, pad)) if pad else x


def _defended_encode_pallas(c, dp_bits, rnd_bits, dp, codec, z, interpret):
    shape = jnp.shape(c)
    flat = jnp.ravel(jnp.asarray(c, jnp.float32))
    n = flat.shape[0]
    block = _BLOCK
    pad = (-n) % block
    grid = (n + pad) // block
    flat = _pad1d(flat, pad)
    zeros = jnp.zeros((n + pad,), jnp.uint32)
    dpb = _pad1d(jnp.ravel(dp_bits), pad) if dp_bits is not None else zeros
    rnb = _pad1d(jnp.ravel(rnd_bits), pad) if rnd_bits is not None else zeros
    has_dp, has_noise = dp is not None, dp_bits is not None
    mech = dp.mechanism if dp is not None else "gaussian"
    sm = jnp.asarray([[dp.clip if has_dp else 0.0,
                       (float(dp.noise_multiplier) * float(dp.clip))
                       if has_noise else 0.0,
                       0.0]], jnp.float32)
    mk = functools.partial(_make_defend_kernel, mechanism=mech,
                           has_dp=has_dp, has_noise=has_noise, codec=codec,
                           has_rnd=rnd_bits is not None, block=block, n=n)
    if codec in ("f32", "bf16"):
        out_dtype = jnp.float32 if codec == "f32" else jnp.bfloat16
        out = _defend_call(mk(stage="cast"), sm, z, flat, dpb, rnb,
                           (n + pad,), out_dtype, block, grid, interpret)
        return out[:n].reshape(shape)
    if codec != "int8":
        raise ValueError(f"no fused encode for codec {codec!r}")
    # pass 1: masked per-block absmax of the defended values (never stored)
    part = _defend_call(mk(stage="absmax"), sm, z, flat, dpb, rnb,
                        (grid, 1, 128), jnp.float32, block, grid, interpret,
                        out_spec=pl.BlockSpec((1, 1, 128),
                                              lambda i: (i, 0, 0)))
    qscale = rounded_quotient(jnp.maximum(jnp.max(part), 1e-12), 127.0, z)
    # pass 2: recompute defended in-register, quantize against qscale
    sm2 = sm.at[0, 2].set(qscale)
    q = _defend_call(mk(stage="quant"), sm2, z, flat, dpb, rnb,
                     (n + pad,), jnp.int8, block, grid, interpret)
    return q[:n].reshape(shape), qscale


def defended_encode(c, dp_bits, rnd_bits, dp, codec: str, *,
                    impl: str = "xla", interpret: bool | None = None,
                    z=None):
    """clip -> noise -> codec-encode one payload from raw PRNG bits.

    ``dp_bits``/``rnd_bits`` are uint32 arrays shaped like ``c`` (or
    None when the stage is off); ``dp`` is a resolved DPConfig or None.
    Both impls return exactly what the unfused
    ``codec.encode(defend_payload(c, ...), ...)`` chain returns, bit for
    bit. ``z`` is the anti-contraction runtime zero; jitted callers must
    pass their own traced copy down (defaulting here is only exact for
    eager calls).
    """
    if z is None:
        z = runtime_zero()
    if impl == "pallas":
        return _defended_encode_pallas(c, dp_bits, rnd_bits, dp, codec, z,
                                       interpret)
    if impl != "xla":
        raise ValueError(f"unknown impl {impl!r}; have xla, pallas")
    return _encode_math(_defend_math(c, dp_bits, dp, z), rnd_bits, codec, z)


# --------------------------------------- the exchange-facing fast paths ----
# Jitted with the exchange static (instances hash by semantics), so one
# eager call from the host executors is ONE dispatch: the key folds
# (_dp_key/_codec_key, including any shard-fold subclass hook), the bits
# draws, and the whole defended-encode chain run inside a single trace.

def _release_bits(ex, c, key):
    """The raw uint32 streams one release consumes, keyed exactly like
    the unfused seam: dp noise off ``ex._dp_key`` (which raises on a
    missing round key, same as the oracle), codec rounding off
    ``ex._codec_key``."""
    shape = jnp.shape(c)
    dp_bits = None
    if ex.dp is not None:
        dp_key = ex._dp_key(key)        # raises on key=None, like the oracle
        if float(ex.dp.noise_multiplier) != 0.0:
            dp_bits = jax.random.bits(dp_key, shape, jnp.uint32)
    rnd_bits = None
    if ex.codec.name == "int8" and key is not None:
        rnd_bits = jax.random.bits(ex._codec_key(key), shape, jnp.uint32)
    return dp_bits, rnd_bits


@functools.partial(jax.jit, static_argnames=("ex", "impl", "interpret"))
def _encode_up_jit(ex, c, key, z, impl, interpret):
    dp_bits, rnd_bits = _release_bits(ex, c, key)
    return defended_encode(c, dp_bits, rnd_bits, ex.dp, ex.codec.name,
                           impl=impl, interpret=interpret, z=z)


def encode_up_fused(ex, c, key, impl: str = "xla",
                    interpret: bool | None = None):
    return _encode_up_jit(ex, c, key, runtime_zero(), impl, interpret)


@functools.partial(jax.jit, static_argnames=("ex", "impl", "interpret"))
def _roundtrip_up_jit(ex, c, key, z, impl, interpret):
    wire = _encode_up_jit(ex, c, key, z, impl, interpret)
    return ex.codec.decode(wire)


def roundtrip_up_fused(ex, c, key, impl: str = "xla",
                       interpret: bool | None = None):
    return _roundtrip_up_jit(ex, c, key, runtime_zero(), impl, interpret)


@functools.partial(jax.jit, static_argnames=("ex",))
def _defend_jit(ex, c, key, z):
    dp_bits, _ = _release_bits(ex, c, key)
    return _defend_math(c, dp_bits, ex.dp, z)


def defend_fused(ex, c, key):
    return _defend_jit(ex, c, key, runtime_zero())


# ------------------------------------------------- perturb / apply side ----

def _leaf_bits(tree, key):
    """The per-leaf (key, bits) split zoo.direction_tree uses — shared so
    the fused paths replay the exact same streams."""
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    bits = [jax.random.bits(k, leaf.shape, jnp.uint32)
            for k, leaf in zip(keys, leaves)]
    return leaves, treedef, bits


def zo_apply(w_tree, key, scale, *, impl: str = "xla",   # zvlint: bit-exact
             interpret: bool | None = None):
    """w - scale * u(key) with Rademacher u regenerated from the seed,
    never stored. ``scale`` is lr*coeff (or -mu for a perturbation).
    Bitwise equal to zoo.apply_zo_update(dist='rademacher') — impl='xla'
    for every dtype, impl='pallas' for f32 leaves (both do f32 math and
    cast out)."""
    leaves, treedef, bits = _leaf_bits(w_tree, key)
    if impl == "pallas":
        outs = [zo_update_pallas(leaf.reshape(-1), b.reshape(-1),
                                 jnp.asarray(scale, jnp.float32),
                                 interpret=interpret).reshape(leaf.shape)
                for leaf, b in zip(leaves, bits)]
    else:
        # zvlint: disable=kernel-float-safety — EAGER oracle formula: this
        # branch dispatches op-by-op, mirroring zoo.apply_zo_update
        # verbatim; guarding it would change the very bits it pins
        outs = [(leaf.astype(jnp.float32)
                 - scale * rademacher_from_bits(b)).astype(leaf.dtype)
                for leaf, b in zip(leaves, bits)]
    return jax.tree.unflatten(treedef, outs)


def perturb(w_tree, key, mu: float, *, impl: str = "xla",   # zvlint: bit-exact
            interpret: bool | None = None):
    """(w + mu*u, u) with Rademacher u — the fused twin of zoo.perturb.
    The xla impl mirrors the oracle's formula exactly (bitwise for every
    dtype); pallas routes through the zo_update kernel at scale=-mu
    (bitwise for f32: subtracting the negated product is IEEE-exact)."""
    leaves, treedef, bits = _leaf_bits(w_tree, key)
    u = jax.tree.unflatten(treedef, [rademacher_from_bits(b) for b in bits])
    if impl == "pallas":
        pert = zo_apply(w_tree, key, np.float32(-mu), impl="pallas",
                        interpret=interpret)
    else:
        # zvlint: disable=kernel-float-safety — EAGER oracle formula,
        # mirroring zoo.perturb verbatim (see zo_apply's xla branch)
        pert = jax.tree.map(lambda w, d: w + mu * d.astype(w.dtype),
                            w_tree, u)
    return pert, u


def zo_gradient_from_seed(w_tree, key, coeff):
    """coeff * u(key) — the fused twin of zoo.zo_gradient_from_seed for
    Rademacher directions (same per-leaf key split, same low-bit law)."""
    _, treedef, bits = _leaf_bits(w_tree, key)
    return jax.tree.unflatten(
        treedef, [coeff * rademacher_from_bits(b) for b in bits])


@jax.jit
def _apply_direction_jit(w, u, coeff, lr, z):   # zvlint: bit-exact
    return jax.tree.map(
        lambda a, d: (a - rounded_product(lr * coeff, d, z)).astype(a.dtype),
        w, u)


def apply_direction_fused(w, u, coeff, lr):
    """One-dispatch dense apply from a materialized direction — the
    jitted twin of ZOExchange.apply_direction (same math, same
    evaluation order; the (lr*coeff)*d product rounds on its own so the
    jitted chain matches the eager oracle bit for bit)."""
    return _apply_direction_jit(w, u, coeff, lr, runtime_zero())
