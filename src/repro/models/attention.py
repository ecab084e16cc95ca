"""Attention: GQA/MHA, causal / sliding-window / bidirectional / cross,
optional QKV-bias and qk-norm, flash-style blocked softmax in pure JAX;
and DeepSeek-V2's multi-head latent attention (MLA) with YaRN rope, whose
decode cache holds the latent and the shared rope key.

Memory discipline: the quadratic score matrix is never materialized for long
sequences — training/prefill use an online-softmax scan over KV blocks
(`blocked_attention`), sliding-window uses a banded q-block scan
(`windowed_attention`). The Pallas kernel in ``repro/kernels/flash_attention``
is the TPU-target version of the same math; these jnp paths are also its
reference oracle.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MLAConfig, ModelConfig
from repro.models.layers import apply_rope, dense_init, rms_norm

NEG_INF = -1e30


# ---------------------------------------------------------------- params ---

def attn_init(key, cfg: ModelConfig, dtype):
    if cfg.mla is not None:
        return mla_init(key, cfg, dtype)
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, \
        cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, H * hd, dtype),
        "wk": dense_init(ks[1], d, KV * hd, dtype),
        "wv": dense_init(ks[2], d, KV * hd, dtype),
        "wo": dense_init(ks[3], H * hd, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((KV * hd,), dtype)
        p["bv"] = jnp.zeros((KV * hd,), dtype)
    if cfg.qk_norm:
        p["q_gamma"] = jnp.ones((hd,), dtype)
        p["k_gamma"] = jnp.ones((hd,), dtype)
    return p


def _project_qkv(p, cfg: ModelConfig, x, positions, rope: bool = True):
    B, S, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = jnp.dot(x, p["wq"])
    k = jnp.dot(x, p["wk"])
    v = jnp.dot(x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_gamma"], cfg.norm_eps)
        k = rms_norm(k, p["k_gamma"], cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


# ------------------------------------------------------- blocked softmax ---

def blocked_attention(q, k, v, *, causal: bool, kv_block: int = 512,
                      q_positions=None, kv_positions=None, scale=None):
    """Online-softmax attention scanning KV blocks; never builds (S,S).

    q, k: (B,Sq|Skv,H|KV,hd); v: (B,Skv,KV,vd) with H = KV*G; scores are
    scaled by ``scale`` (default hd**-0.5). Returns (B,Sq,H,vd).
    """
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    vd = v.shape[-1]
    G = H // KV
    if q_positions is None:
        q_positions = jnp.arange(Sq)[None, :].repeat(B, 0)
    if kv_positions is None:
        kv_positions = jnp.arange(Skv)[None, :].repeat(B, 0)
    kv_block = min(kv_block, Skv)
    while Skv % kv_block:
        kv_block //= 2
    nblocks = Skv // kv_block
    # keep operands in model dtype; accumulate in f32 (MXU semantics) —
    # halves HBM/ICI bytes vs upcasting the operands (§Perf iteration A2)
    qg = q.reshape(B, Sq, KV, G, hd)
    if scale is None:
        scale = 1.0 / np.sqrt(hd)

    def body(carry, blk):
        m, l, acc = carry
        kb, vb, posb = blk                       # (B,kb,KV,hd), (B,kb)
        s = jnp.einsum("bqkgh,bskh->bqkgs", qg, kb,
                       preferred_element_type=jnp.float32)
        s = s * scale
        mask = posb[:, None, :] <= q_positions[:, :, None]  # (B,Sq,kb)
        if causal:
            s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bqkgs,bskh->bqkgh", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    k_b = k.reshape(B, nblocks, kv_block, KV, hd).swapaxes(0, 1)
    v_b = v.reshape(B, nblocks, kv_block, KV, vd).swapaxes(0, 1)
    pos_b = kv_positions.reshape(B, nblocks, kv_block).swapaxes(0, 1)
    m0 = jnp.full((B, Sq, KV, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, KV, G), jnp.float32)
    a0 = jnp.zeros((B, Sq, KV, G, vd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (k_b, v_b, pos_b))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, Sq, H, vd).astype(q.dtype)


def windowed_attention(q, k, v, window: int, *, q_block: int = 512):
    """Banded causal attention: position t attends to (t-window, t].

    Scans q blocks; each block attends to a dynamic slice of K/V of length
    (window + q_block) ending at the block end. FLOPs O(S * window).
    """
    B, S, H, hd = q.shape
    _, _, KV, _ = k.shape
    G = H // KV
    q_block = min(q_block, S)
    while S % q_block:
        q_block //= 2
    nq = S // q_block
    span = window + q_block
    scale = 1.0 / np.sqrt(hd)
    # Left-pad K/V so every slice is in-bounds; padded positions get -inf.
    kp = jnp.pad(k, ((0, 0), (span - q_block, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (span - q_block, 0), (0, 0), (0, 0)))

    def body(_, i):
        q_start = i * q_block
        qb = jax.lax.dynamic_slice_in_dim(q, q_start, q_block, axis=1)
        kb = jax.lax.dynamic_slice_in_dim(kp, q_start, span, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(vp, q_start, span, axis=1)
        qg = qb.reshape(B, q_block, KV, G, hd)
        s = jnp.einsum("bqkgh,bskh->bqkgs", qg, kb,
                       preferred_element_type=jnp.float32) * scale
        q_pos = q_start + jnp.arange(q_block)
        kv_pos = q_start - (span - q_block) + jnp.arange(span)
        ok = (kv_pos[None, :] <= q_pos[:, None]) & \
             (kv_pos[None, :] > q_pos[:, None] - window) & \
             (kv_pos[None, :] >= 0)
        s = jnp.where(ok[None, :, None, None, :], s, NEG_INF)
        out = jax.nn.softmax(s, axis=-1)
        ob = jnp.einsum("bqkgs,bskh->bqkgh", out.astype(vb.dtype), vb,
                        preferred_element_type=jnp.float32)
        return None, ob.reshape(B, q_block, H, hd).astype(q.dtype)

    _, blocks = jax.lax.scan(body, None, jnp.arange(nq))
    return blocks.swapaxes(0, 1).reshape(B, S, H, hd)


def decode_attention(q, k_cache, v_cache, cache_len):
    """Single-token attention against a (possibly rolling) cache.

    q: (B,1,H,hd); caches: (B,Smax,KV,hd); cache_len: valid prefix length —
    a scalar or a per-slot (B,) vector (continuous batching). Positions
    >= cache_len are masked.
    """
    B, _, H, hd = q.shape
    _, Smax, KV, _ = k_cache.shape
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg, k_cache,
                   preferred_element_type=jnp.float32) / np.sqrt(hd)
    cache_len = jnp.broadcast_to(jnp.asarray(cache_len), (B,))
    valid = jnp.arange(Smax)[None, :] < cache_len[:, None]  # (B,Smax)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskh->bkgh", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, 1, H, hd).astype(q.dtype)


# ----------------------------------------------------------- module apis ---

def attn_apply(p, cfg: ModelConfig, x, positions, *, causal=True,
               kv_block: int = 512):
    """Full-sequence self-attention (train / prefill)."""
    if cfg.mla is not None:
        return mla_apply(p, cfg, x, positions, causal=causal,
                         kv_block=kv_block)
    q, k, v = _project_qkv(p, cfg, x, positions, rope=cfg.pos_emb == "rope")
    if cfg.sliding_window is not None and causal:
        o = windowed_attention(q, k, v, cfg.sliding_window)
    else:
        o = blocked_attention(q, k, v, causal=causal, kv_block=kv_block,
                              q_positions=positions, kv_positions=positions)
    B, S = x.shape[:2]
    return jnp.dot(o.reshape(B, S, -1), p["wo"]), (k, v)


def cross_attn_init(key, cfg: ModelConfig, dtype):
    return attn_init(key, cfg, dtype)


def cross_attn_apply(p, cfg: ModelConfig, x, enc_kv):
    """Decoder cross-attention; enc_kv = (k,v) precomputed from encoder."""
    B, S, _ = x.shape
    H, hd = cfg.num_heads, cfg.resolved_head_dim
    q = jnp.dot(x, p["wq"]).reshape(B, S, H, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_gamma"], cfg.norm_eps)
    k, v = enc_kv
    o = blocked_attention(q, k, v, causal=False)
    return jnp.dot(o.reshape(B, S, -1), p["wo"])


def encode_kv(p, cfg: ModelConfig, enc_out):
    """Project encoder output once into cross-attention K/V."""
    B, F, _ = enc_out.shape
    KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    k = jnp.dot(enc_out, p["wk"]).reshape(B, F, KV, hd)
    v = jnp.dot(enc_out, p["wv"]).reshape(B, F, KV, hd)
    if cfg.qkv_bias:
        pass  # biases folded in _project_qkv only for self-attn path
    if cfg.qk_norm:
        k = rms_norm(k, p["k_gamma"], cfg.norm_eps)
    return k, v


def _quantize_kv(t):
    """(B,KV,hd) -> (int8 values, per-(B,KV) f32 scale)."""
    amax = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale[..., 0]


def attn_decode_step(p, cfg: ModelConfig, x, cache, pos):
    """One decode step. x: (B,1,d). cache: {"k","v"} (B,Smax,KV,hd)
    [+ {"k_scale","v_scale"} (B,Smax,KV) for the int8 cache].

    With a sliding window the cache is a rolling buffer of size window and
    `pos` indexes modulo-window; RoPE uses absolute positions.
    """
    if cfg.mla is not None:
        return mla_decode_step(p, cfg, x, cache, pos)
    B = x.shape[0]
    pos = jnp.asarray(pos)
    pos_b = jnp.broadcast_to(pos, (B,))        # per-slot positions OK
    positions = pos_b[:, None]
    q, k, v = _project_qkv(p, cfg, x, positions, rope=cfg.pos_emb == "rope")
    Smax = cache["k"].shape[1]
    slot = pos_b % Smax if cfg.sliding_window is not None else pos_b
    bidx = jnp.arange(B)
    new_cache = dict(cache)
    if cfg.kv_cache_dtype == "int8":
        kq, ks = _quantize_kv(k[:, 0])
        vq, vs = _quantize_kv(v[:, 0])
        new_cache["k"] = cache["k"].at[bidx, slot].set(kq)
        new_cache["v"] = cache["v"].at[bidx, slot].set(vq)
        new_cache["k_scale"] = cache["k_scale"].at[bidx, slot].set(ks)
        new_cache["v_scale"] = cache["v_scale"].at[bidx, slot].set(vs)
        # dequantize lazily inside the attention einsums: scores use the
        # int8 values and fold the scale in afterwards
        k_eff = (new_cache["k"].astype(q.dtype)
                 * new_cache["k_scale"][..., None].astype(q.dtype))
        v_eff = (new_cache["v"].astype(q.dtype)
                 * new_cache["v_scale"][..., None].astype(q.dtype))
    else:
        new_cache["k"] = cache["k"].at[bidx, slot].set(
            k[:, 0].astype(cache["k"].dtype))
        new_cache["v"] = cache["v"].at[bidx, slot].set(
            v[:, 0].astype(cache["v"].dtype))
        k_eff, v_eff = new_cache["k"], new_cache["v"]
    cache_len = jnp.minimum(pos_b + 1, Smax)
    o = decode_attention(q, k_eff, v_eff, cache_len)
    out = jnp.dot(o.reshape(B, 1, -1), p["wo"])
    return out, new_cache


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    if cfg.mla is not None:
        a = cfg.mla
        return {"ckv": jnp.zeros((batch, max_len, a.kv_lora_rank), dtype),
                "kpe": jnp.zeros((batch, max_len, a.qk_rope_head_dim),
                                 dtype)}
    Smax = max_len if cfg.sliding_window is None \
        else min(max_len, cfg.sliding_window)
    KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    if cfg.kv_cache_dtype == "int8":
        return {
            "k": jnp.zeros((batch, Smax, KV, hd), jnp.int8),
            "v": jnp.zeros((batch, Smax, KV, hd), jnp.int8),
            "k_scale": jnp.zeros((batch, Smax, KV), jnp.float32),
            "v_scale": jnp.zeros((batch, Smax, KV), jnp.float32),
        }
    return {
        "k": jnp.zeros((batch, Smax, KV, hd), dtype),
        "v": jnp.zeros((batch, Smax, KV, hd), dtype),
    }


# ------------------------------------------------- latent attention (MLA) ---
# DeepSeek-V2 (modeling_deepseek.py: DeepseekV2Attention with no q-LoRA,
# DeepseekV2YarnRotaryEmbedding). Per head the query is [q_nope | q_pe];
# the keys and values come up from one RMS-normed latent c shared by all
# heads, and the rope key k_pe is one head shared by all.

def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(a: MLAConfig, theta: float) -> np.ndarray:
    """YaRN's inverse frequencies of the rope dims: the extrapolated and
    the interpolated (over ``rope_factor``) blended by the linear ramp
    between the correction dims of ``beta_fast`` and ``beta_slow``."""
    dim = a.qk_rope_head_dim
    exps = np.arange(0, dim, 2, dtype=np.float32) / dim
    extra = 1.0 / theta ** exps
    if a.rope_factor <= 1:
        return extra.astype(np.float32)
    inter = 1.0 / (a.rope_factor * theta ** exps)

    def corr(rotations):
        return (dim * math.log(a.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(a.beta_fast)), 0)
    high = min(math.ceil(corr(a.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    keep = 1.0 - ramp                  # 1 where the frequency extrapolates
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def mla_softmax_scale(a: MLAConfig) -> float:
    scale = a.qk_head_dim ** -0.5
    if a.mscale_all_dim:
        m = _yarn_mscale(a.rope_factor, a.mscale_all_dim)
        scale *= m * m
    return scale


def _mla_rope(x, positions, cfg: ModelConfig):
    """x: (B,S,h,rope); DeepSeek de-interleaves the rope dims (even, then
    odd) before rotating halves. cos and sin carry YaRN's mscale ratio."""
    a = cfg.mla
    inv = jnp.asarray(yarn_inv_freq(a, cfg.rope_theta))
    m = (_yarn_mscale(a.rope_factor, a.mscale)
         / _yarn_mscale(a.rope_factor, a.mscale_all_dim))
    ang = positions.astype(jnp.float32)[..., None] * inv   # (B,S,rope/2)
    cos = (jnp.cos(ang) * m)[..., None, :]
    sin = (jnp.sin(ang) * m)[..., None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., 0::2], x32[..., 1::2]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def mla_init(key, cfg: ModelConfig, dtype):
    d, H, a = cfg.d_model, cfg.num_heads, cfg.mla
    r = a.kv_lora_rank
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d, H * a.qk_head_dim, dtype),
        "wkv_a": dense_init(ks[1], d, r + a.qk_rope_head_dim, dtype),
        "kv_norm": jnp.ones((r,), dtype),
        "wkv_b": dense_init(ks[2], r, H * (a.qk_nope_head_dim
                                           + a.v_head_dim), dtype),
        "wo": dense_init(ks[3], H * a.v_head_dim, d, dtype),
    }


def _mla_project(p, cfg: ModelConfig, x, positions):
    """(q_nope (B,S,H,nope), q_pe (B,S,H,rope), c (B,S,r), k_pe
    (B,S,1,rope)), the rope parts rotated."""
    B, S, _ = x.shape
    a, H = cfg.mla, cfg.num_heads
    q = jnp.dot(x, p["wq"]).reshape(B, S, H, a.qk_head_dim)
    ckv = jnp.dot(x, p["wkv_a"])
    c = rms_norm(ckv[..., :a.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_pe = ckv[..., None, a.kv_lora_rank:]
    return (q[..., :a.qk_nope_head_dim],
            _mla_rope(q[..., a.qk_nope_head_dim:], positions, cfg), c,
            _mla_rope(k_pe, positions, cfg))


def mla_apply(p, cfg: ModelConfig, x, positions, *, causal=True,
              kv_block: int = 512):
    """Full sequence: k and v expanded from the latent, the shared rope
    key broadcast to every head, one softmax over the 192-wide scores."""
    B, S, _ = x.shape
    a, H = cfg.mla, cfg.num_heads
    with jax.named_scope("mla"):
        q_nope, q_pe, c, k_pe = _mla_project(p, cfg, x, positions)
        kv = jnp.dot(c, p["wkv_b"]).reshape(
            B, S, H, a.qk_nope_head_dim + a.v_head_dim)
        q = jnp.concatenate([q_nope, q_pe], -1)
        k = jnp.concatenate([kv[..., :a.qk_nope_head_dim],
                             jnp.broadcast_to(k_pe, q_pe.shape)], -1)
        o = blocked_attention(q, k, kv[..., a.qk_nope_head_dim:],
                              causal=causal, kv_block=kv_block,
                              q_positions=positions, kv_positions=positions,
                              scale=mla_softmax_scale(a))
        out = jnp.dot(o.reshape(B, S, H * a.v_head_dim), p["wo"])
    return out, (c, k_pe)


def mla_decode_step(p, cfg: ModelConfig, x, cache, pos):
    """One token against the latent cache {"ckv": (B,Smax,r), "kpe":
    (B,Smax,rope)}: the up-projection is absorbed into the query (scores
    against c) and into the output (values from c), so the cache holds
    r + rope numbers a token."""
    B = x.shape[0]
    a, H = cfg.mla, cfg.num_heads
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (B,))
    with jax.named_scope("mla"):
        q_nope, q_pe, c, k_pe = _mla_project(p, cfg, x, pos_b[:, None])
        bidx = jnp.arange(B)
        ckv = cache["ckv"].at[bidx, pos_b].set(
            c[:, 0].astype(cache["ckv"].dtype))
        kpe = cache["kpe"].at[bidx, pos_b].set(
            k_pe[:, 0, 0].astype(cache["kpe"].dtype))
        w = p["wkv_b"].reshape(a.kv_lora_rank, H,
                               a.qk_nope_head_dim + a.v_head_dim)
        f32 = jnp.float32
        q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0],
                           w[..., :a.qk_nope_head_dim],
                           preferred_element_type=f32)
        s = (jnp.einsum("bhr,bsr->bhs", q_lat, ckv,
                        preferred_element_type=f32)
             + jnp.einsum("bhp,bsp->bhs", q_pe[:, 0], kpe,
                          preferred_element_type=f32))
        s = s * mla_softmax_scale(a)
        valid = jnp.arange(ckv.shape[1])[None, :] <= pos_b[:, None]
        s = jnp.where(valid[:, None, :], s, NEG_INF)
        o_lat = jnp.einsum("bhs,bsr->bhr", jax.nn.softmax(s, axis=-1), ckv,
                           preferred_element_type=f32)
        o = jnp.einsum("bhr,rhv->bhv", o_lat, w[..., a.qk_nope_head_dim:],
                       preferred_element_type=f32).astype(x.dtype)
        out = jnp.dot(o.reshape(B, 1, H * a.v_head_dim), p["wo"])
    return out, {"ckv": ckv, "kpe": kpe}
