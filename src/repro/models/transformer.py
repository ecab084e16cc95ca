"""Per-family layer blocks + scan-over-layers stacks.

Every architecture family reduces to one homogeneous block type so the whole
depth is a single ``lax.scan`` over stacked layer params (HLO size O(1) in
depth; required for the 80-dry-run compile budget). Blocks are rematerialized
(``jax.checkpoint``) during training when cfg.remat. A model whose first
``first_k_dense`` layers are dense (DeepSeek-V2) runs those as single
blocks ahead of the scanned stack (``prefix_forward``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import mamba as mb
from repro.models import moe as moe_mod
from repro.models import ssm as rwkv
from repro.models.layers import rms_norm, swiglu_apply, swiglu_init
from repro.sharding.ctx import constrain


# ------------------------------------------------------------- layer init ---

def layer_init(key, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 4)
    p = {"norm1": jnp.ones((cfg.d_model,), dtype),
         "norm2": jnp.ones((cfg.d_model,), dtype)}
    fam = cfg.family
    if fam in ("dense", "vlm", "audio"):
        p["attn"] = attn.attn_init(ks[0], cfg, dtype)
        p["mlp"] = swiglu_init(ks[1], cfg.d_model, cfg.d_ff, dtype)
        if cfg.enc_dec:
            p["cross"] = attn.cross_attn_init(ks[2], cfg, dtype)
            p["norm3"] = jnp.ones((cfg.d_model,), dtype)
    elif fam == "moe":
        p["attn"] = attn.attn_init(ks[0], cfg, dtype)
        p["moe"] = moe_mod.moe_init(ks[1], cfg, dtype)
    elif fam == "ssm":
        p["tmix"] = rwkv.rwkv_time_mix_init(ks[0], cfg, dtype)
        p["cmix"] = rwkv.rwkv_channel_mix_init(ks[1], cfg, dtype)
    elif fam == "hybrid":
        p["attn"] = attn.attn_init(ks[0], cfg, dtype)
        p["mamba"] = mb.mamba_init(ks[1], cfg, dtype)
        p["mlp"] = swiglu_init(ks[2], cfg.d_model, cfg.d_ff, dtype)
    else:
        raise ValueError(f"unknown family {fam}")
    return p


def stacked_layers_init(key, cfg: ModelConfig, dtype, n_layers: int):
    keys = jax.random.split(key, n_layers)
    return jax.vmap(lambda k: layer_init(k, cfg, dtype))(keys)


# ---------------------------------------------------------- forward (seq) ---

def block_forward(p, cfg: ModelConfig, x, positions, enc_out=None,
                  causal=True):
    """One layer, full sequence. Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    fam = cfg.family
    if fam == "ssm":
        h, _ = rwkv.rwkv_time_mix_apply(
            p["tmix"], cfg, rms_norm(x, p["norm1"], cfg.norm_eps))
        x = x + h.astype(x.dtype)
        h, _ = rwkv.rwkv_channel_mix_apply(
            p["cmix"], rms_norm(x, p["norm2"], cfg.norm_eps))
        return x + h.astype(x.dtype), aux
    xn = rms_norm(x, p["norm1"], cfg.norm_eps)
    if fam == "hybrid":
        a, _ = attn.attn_apply(p["attn"], cfg, xn, positions, causal=causal)
        m, _ = mb.mamba_apply(p["mamba"], cfg, xn)
        x = x + (0.5 * (a.astype(jnp.float32) + m.astype(jnp.float32))
                 ).astype(x.dtype)
    else:
        a, _ = attn.attn_apply(p["attn"], cfg, xn, positions, causal=causal)
        x = x + a.astype(x.dtype)
    if cfg.enc_dec and enc_out is not None and "cross" in p:
        xn = rms_norm(x, p["norm3"], cfg.norm_eps)
        kv = attn.encode_kv(p["cross"], cfg, enc_out)
        x = x + attn.cross_attn_apply(p["cross"], cfg, xn, kv)
    xn = rms_norm(x, p["norm2"], cfg.norm_eps)
    if fam == "moe":
        h, aux = moe_mod.moe_apply(p["moe"], cfg, xn)
    else:
        h = swiglu_apply(p["mlp"], xn)
    return x + h.astype(x.dtype), aux


def stack_forward(stacked, cfg: ModelConfig, x, positions, enc_out=None,
                  causal=True):
    """Scan the whole stack. Returns (x, total_aux)."""
    fn = functools.partial(block_forward, cfg=cfg, positions=positions,
                           enc_out=enc_out, causal=causal)

    def body(carry, p_l):
        x, aux = carry
        x, a = fn(p_l, x=x)
        x = constrain(x, ("batch", None, None))
        return (x, aux + a), None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    (x, aux), _ = jax.lax.scan(body_fn, (x, jnp.zeros((), jnp.float32)),
                               stacked)
    return x, aux


def dense_config(cfg: ModelConfig) -> ModelConfig:
    """The leading dense layers' config: the model's attention, a SwiGLU
    of width d_ff."""
    return cfg.replace(family="dense", moe=None, first_k_dense=0)


def prefix_forward(stacked, cfg: ModelConfig, x, positions, causal=True):
    """The leading dense layers, one block each, outside the scan."""
    dcfg = dense_config(cfg)

    def one(p_l, x):
        return block_forward(p_l, dcfg, x, positions, causal=causal)[0]

    one = jax.checkpoint(one) if cfg.remat else one
    for i in range(cfg.first_k_dense):
        x = one(jax.tree.map(lambda a: a[i], stacked), x)
        x = constrain(x, ("batch", None, None))
    return x


# -------------------------------------------------------------- decode -----

def layer_cache_init(cfg: ModelConfig, batch: int, max_len: int, dtype):
    fam = cfg.family
    if fam == "ssm":
        return rwkv.rwkv_state_init(cfg, batch)
    c = {"kv": attn.init_kv_cache(cfg, batch, max_len, dtype)}
    if fam == "hybrid":
        c["ssm"] = mb.mamba_state_init(cfg, batch)
    return c


def stacked_cache_init(cfg: ModelConfig, batch: int, max_len: int, dtype,
                       n_layers: int):
    one = layer_cache_init(cfg, batch, max_len, dtype)
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (n_layers,) + a.shape)
        .astype(a.dtype), one)


def block_decode(p, cfg: ModelConfig, x, cache, pos, cross_kv=None):
    """One layer, one token. Returns (x, new_cache)."""
    fam = cfg.family
    if fam == "ssm":
        st = {"S": cache["S"], "x_prev": cache["x_prev"]}
        h, st = rwkv.rwkv_time_mix_decode(
            p["tmix"], cfg, rms_norm(x, p["norm1"], cfg.norm_eps), st)
        x = x + h.astype(x.dtype)
        xn = rms_norm(x, p["norm2"], cfg.norm_eps)
        h, xp = rwkv.rwkv_channel_mix_apply(
            p["cmix"], xn, cache["x_prev_ffn"].astype(xn.dtype))
        x = x + h.astype(x.dtype)
        return x, {"S": st["S"], "x_prev": st["x_prev"],
                   "x_prev_ffn": xp.astype(jnp.float32)}
    new_cache = dict(cache)
    xn = rms_norm(x, p["norm1"], cfg.norm_eps)
    if fam == "hybrid":
        a, new_cache["kv"] = attn.attn_decode_step(p["attn"], cfg, xn,
                                                   cache["kv"], pos)
        m, new_cache["ssm"] = mb.mamba_decode(p["mamba"], cfg, xn,
                                              cache["ssm"])
        x = x + (0.5 * (a.astype(jnp.float32) + m.astype(jnp.float32))
                 ).astype(x.dtype)
    else:
        a, new_cache["kv"] = attn.attn_decode_step(p["attn"], cfg, xn,
                                                   cache["kv"], pos)
        x = x + a.astype(x.dtype)
    if cfg.enc_dec and cross_kv is not None and "cross" in p:
        xn = rms_norm(x, p["norm3"], cfg.norm_eps)
        x = x + attn.cross_attn_apply(p["cross"], cfg, xn, cross_kv)
    xn = rms_norm(x, p["norm2"], cfg.norm_eps)
    if fam == "moe":
        h, _ = moe_mod.moe_apply(p["moe"], cfg, xn)
    else:
        h = swiglu_apply(p["mlp"], xn)
    return x + h.astype(x.dtype), new_cache


def stack_decode(stacked, cfg: ModelConfig, x, caches, pos, cross_kv=None):
    """Scan over layers carrying x, threading per-layer caches as xs/ys.

    cross_kv, when given, is a stacked (L,...) pair of per-layer encoder K/V.
    """
    def body(x, inp):
        if cross_kv is not None:
            p_l, cache_l, ckv_l = inp
        else:
            p_l, cache_l = inp
            ckv_l = None
        x, new_cache = block_decode(p_l, cfg, x, cache_l, pos, cross_kv=ckv_l)
        return x, new_cache

    xs = (stacked, caches, cross_kv) if cross_kv is not None \
        else (stacked, caches)
    x, new_caches = jax.lax.scan(body, x, xs)
    return x, new_caches
