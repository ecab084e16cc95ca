"""Blocked online-softmax (flash) attention kernel — TPU target.

Grid (batch*heads, n_q_blocks, n_kv_blocks); the kv dimension is the
innermost (sequential on TPU), so the running max/denominator/accumulator
live in VMEM scratch across kv steps. Causal masking is done with in-block
iota; fully-masked blocks short-circuit via pl.when (on the dry-run HLO the
scan-counted flops still include them — the kernel is where the 2x causal
overcount actually disappears on hardware).

Layout: q,k,v as (BH, S, hd) — GQA group expansion happens in ops.py.
Tiles: q-block 128 x kv-block 128 x full head_dim (<=128), fp32 softmax.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import interpret_mode

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, bq: int, bkv: int, n_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    kv_start = ki * bkv

    run = True
    if causal:
        # kv block strictly after the q block's last row: fully masked
        run = kv_start <= q_start + bq - 1

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # (bq, hd)
        k = k_ref[0].astype(jnp.float32)            # (bkv, hd)
        v = v_ref[0].astype(jnp.float32)
        # fp32 softmax needs f32 scores: HIGHEST keeps the MXU from
        # rounding the operands to one bf16 pass on the chip
        hi = jax.lax.Precision.HIGHEST
        s = jnp.dot(q, k.T, precision=hi,
                    preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                       (bq, bkv), 0)
            k_pos = kv_start + jax.lax.broadcasted_iota(jnp.int32,
                                                        (bq, bkv), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, precision=hi, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _done():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bkv",
                                             "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True, bq: int = 128,
                           bkv: int = 128, interpret: bool | None = None):
    """q,k,v: (BH, S, hd) same-head layout. Returns (BH, S, hd)."""
    BH, S, hd = q.shape
    bq, bkv = min(bq, S), min(bkv, S)
    assert S % bq == 0 and S % bkv == 0
    scale = 1.0 / (hd ** 0.5)
    grid = (BH, S // bq, S // bkv)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, bq=bq,
                          bkv=bkv, n_kv=S // bkv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(q, k, v)
