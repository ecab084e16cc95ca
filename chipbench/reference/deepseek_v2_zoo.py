"""Plain reference of one AsyREVEL round over a DeepSeek-V2 server (one
chip's share of it) with q party embedding towers (the vfl-zoo step),
written from the published model description (DeepSeek-V2's
``modeling_deepseek.py``: DeepseekV2Attention without q-LoRA,
DeepseekV2YarnRotaryEmbedding, MoEGate, DeepseekV2MoE) and the paper's
Algorithm 1. It imports nothing of the program. The round itself, the
key schedule, the directions and the party towers are those of
``qwen2_zoo.py``, whose precision rules it keeps: every matmul at
HIGHEST on operands rounded to ``operands`` ("f32" for the reference,
"fp8" for the control, "bf16" the witness); stored bf16 parameters are
perturbed and updated with the configuration's rounding.

The server, layer by layer: RMSNorm, MLA, residual; RMSNorm, then the
dense SwiGLU in the first ``first_k_dense_replace`` layers and the
expert layer after them, residual; final RMSNorm; the untied head. It
reads the server tree by the names the benchmark's weights carry
(``dense_layers``, ``layers``, ``final_norm``, ``lm_head``).

Departures from the published model, the program's alike:
- one chip's share: the routed experts are ``experts_held`` of the
  ``n_routed_experts`` (experts 0 to held - 1). The router scores all of
  them and picks the top-k; the slots whose expert is not held add
  nothing. Each held expert is computed here on every token and weighted
  by its routing weight (0 where not chosen), not on a sorted dispatch;
- the vocabulary is the configuration's slice (``vocab_size``), the loss
  a mean over its tokens;
- h is the token cross-entropy: the published sequence-level balance
  loss is left out (its coefficient is not in the config);
- the server holds no input embedding: the party towers give its
  inputs.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.qwen2_zoo import (  # noqa: F401
    ALSO, CONTROL, HI, _mm, _rms, directions, draws, fold_name, party_out)


def _yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn(m: dict):
    """(inverse frequencies of the rope dims, cos/sin factor, softmax
    scale), as DeepseekV2YarnRotaryEmbedding and DeepseekV2Attention set
    them."""
    rs, dim, base = m["rope_scaling"], m["qk_rope_head_dim"], m["rope_theta"]
    factor, orig = rs["factor"], rs["original_max_position_embeddings"]

    def corr_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) \
            / (2 * math.log(base))

    low = max(math.floor(corr_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = extra / factor
    mask = 1.0 - ramp
    inv = inter * (1 - mask) + extra * mask
    cs = (_yarn_mscale(factor, rs["mscale"])
          / _yarn_mscale(factor, rs["mscale_all_dim"]))
    scale = (m["qk_nope_head_dim"] + dim) ** -0.5
    if rs["mscale_all_dim"]:
        scale *= _yarn_mscale(factor, rs["mscale_all_dim"]) ** 2
    return inv, cs, scale


def _rope(x, inv, cs):
    """x (B, S, h, rope): DeepSeek's apply_rotary_pos_emb, which first
    de-interleaves the dims (even ones, then odd) and then rotates
    halves."""
    B, S, h, dim = x.shape
    x = x.reshape(B, S, h, dim // 2, 2).swapaxes(-1, -2).reshape(
        B, S, h, dim)
    ang = np.arange(S)[:, None] * inv[None, :]
    emb = np.concatenate([ang, ang], -1)
    cos = jnp.asarray(np.cos(emb) * cs, jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(emb) * cs, jnp.float32)[None, :, None, :]
    rot = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    return x * cos + rot * sin


def _swiglu(x, p, operands):
    g = _mm("nd,df->nf", x, p["w_gate"], operands)
    u = _mm("nd,df->nf", x, p["w_up"], operands)
    return _mm("nf,fd->nd", jax.nn.silu(g) * u, p["w_down"], operands)


def route(x, router, m: dict, operands="f32"):
    """MoEGate on rows x (N, d): (N, n_routed) weights, each row holding
    its top-k softmax probabilities (greedy; not renormalized, as
    norm_topk_prob false, the only value the family runs) times
    routed_scaling_factor and 0 elsewhere."""
    probs = jax.nn.softmax(_mm("nd,de->ne", x, router, operands), axis=-1)
    above = jnp.sum(probs[:, None, :] > probs[:, :, None], axis=-1)
    w = jnp.where(above < m["num_experts_per_tok"], probs, 0.0)
    return w * m["routed_scaling_factor"]


def experts(x, p, m: dict, operands="f32"):
    """DeepseekV2MoE on rows x (N, d), the held routed experts' part plus
    the shared experts."""
    held = m["experts_held"]
    w = route(x, p["router"], m, operands)[:, :held]         # (N, held)
    g = _mm("nd,edf->enf", x, p["w_gate"], operands)
    u = _mm("nd,edf->enf", x, p["w_up"], operands)
    y = _mm("enf,efd->end", jax.nn.silu(g) * u, p["w_down"], operands)
    return jnp.einsum("ne,end->nd", w, y, precision=HI) \
        + _swiglu(x, p["shared"], operands)


def server_loss(w0, x, targets, m: dict, operands="f32", rows=None):
    """Token-mean cross-entropy of the server model on input embeddings
    x (B, S, d), over the first ``rows`` rows (all by default)."""
    if rows is not None:
        x, targets = x[:rows], targets[:rows]
    B, S, d = x.shape
    H, eps = m["num_attention_heads"], m["rms_norm_eps"]
    r, nope, vd = m["kv_lora_rank"], m["qk_nope_head_dim"], m["v_head_dim"]
    inv, cs, scale = yarn(m)
    mask = jnp.tril(jnp.ones((S, S), bool))

    def attention(xn, a):
        q = _mm("bsd,de->bse", xn, a["wq"], operands).reshape(B, S, H, -1)
        ckv = _mm("bsd,de->bse", xn, a["wkv_a"], operands)
        c = _rms(ckv[..., :r], a["kv_norm"], eps)
        kv = _mm("bsr,re->bse", c, a["wkv_b"], operands).reshape(
            B, S, H, -1)
        q_pe = _rope(q[..., nope:], inv, cs)
        k_pe = _rope(ckv[..., None, r:], inv, cs)          # one head, shared
        s = (_mm("bqhe,bkhe->bhqk", q[..., :nope], kv[..., :nope], operands)
             + _mm("bqhe,bkxe->bhqk", q_pe, k_pe, operands)) * scale
        s = jnp.where(mask[None, None], s, -jnp.inf)
        o = _mm("bhqk,bkhe->bqhe", jax.nn.softmax(s, axis=-1), kv[..., nope:],
                operands)
        return _mm("bse,ed->bsd", o.reshape(B, S, H * vd), a["wo"], operands)

    def layer(x, p, ffn):
        x = x + attention(_rms(x, p["norm1"], eps), p["attn"])
        xn = _rms(x, p["norm2"], eps).reshape(B * S, d)
        return x + ffn(xn, p).reshape(B, S, d)

    x = x.astype(jnp.float32)
    dense = w0.get("dense_layers")
    for i in range(m["first_k_dense_replace"]):
        x = layer(x, jax.tree.map(lambda a: a[i], dense),
                  lambda xn, p: _swiglu(xn, p["mlp"], operands))
    x, _ = jax.lax.scan(
        lambda x, p: (layer(x, p, lambda xn, p: experts(xn, p["moe"], m,
                                                        operands)), None),
        x, w0["layers"])
    x = _rms(x, w0["final_norm"], eps)

    def row_loss(row):
        """One sequence's summed cross-entropy, one row's f32 logits at a
        time."""
        xr, tr = row
        logits = _mm("sd,dv->sv", xr, w0["lm_head"], operands)
        logz = jax.nn.logsumexp(logits, axis=-1)
        return jnp.sum(logz - jnp.take_along_axis(logits, tr[:, None],
                                                  axis=-1)[:, 0])

    return jnp.sum(jax.lax.map(row_loss, (x, targets))) / (B * S)


def _thaw(m, v):
    return ({k: dict(x) if isinstance(x, tuple) else x for k, x in m},
            dict(v))


@functools.partial(jax.jit, static_argnames=("m", "v", "operands", "rows"))
def _party_round(w0, stale, fresh, step_key, tokens, targets, m_t, *, m, v,
                 operands, rows):
    """h, the party's coefficient and its new block, and the c table."""
    m, v = _thaw(m, v)
    q, mu, lam = v["num_parties"], v["mu"], v["lam"]
    B, S = tokens.shape
    cs = jnp.stack([party_out(jax.tree.map(lambda a: a[j], stale), tokens,
                              operands) for j in range(q)], axis=2)
    loss = functools.partial(server_loss, targets=targets, m=m,
                             operands=operands, rows=rows)
    h = loss(w0, cs.reshape(B, S, -1))
    u = directions(fold_name(step_key, "u"), fresh)
    pert = jax.tree.map(lambda w, d: w + mu * d, fresh, u)
    c_hat = party_out(pert, tokens, operands)
    cs_hat = cs.at[:, :, m_t].set(c_hat)
    h_bar = loss(w0, cs_hat.reshape(B, S, -1))
    # the party's own regularizer is zero for this model; lam multiplies 0
    coeff = ((h_bar + lam * 0.0) - (h + lam * 0.0)) / mu
    new_fresh = jax.tree.map(lambda w, d: w - v["lr_party"] * coeff * d,
                             fresh, u)
    return h, coeff, new_fresh, cs


@functools.partial(jax.jit, static_argnames=("m", "v", "operands", "rows"))
def _h_hat(w0, cs, step_key, targets, *, m, v, operands, rows):
    """The loss at w0 + mu u0 on the same c table."""
    m, v = _thaw(m, v)
    u0 = directions(fold_name(step_key, "u0"), w0)
    w0p = jax.tree.map(
        lambda w, d: w + (v["mu"] * d.astype(w.dtype)).astype(w.dtype), w0,
        u0)
    B, S = targets.shape
    return server_loss(w0p, cs.reshape(B, S, -1), targets, m, operands, rows)


def _server_update(w0, step_key, coeff0, *, v):
    """w0 - lr coeff0 u0, the directions drawn again: the server's bf16
    tree is twice the size of its f32 directions, and a chip that holds
    the reference beside the check's states has room for one of them
    at a time (the layout of ``qwen2_zoo.round_core`` in three calls)."""
    v = dict(v)
    u0 = directions(fold_name(step_key, "u0"), w0)
    return jax.tree.map(
        lambda w, d: (w.astype(jnp.float32) - v["lr_server"] * coeff0 * d
                      ).astype(w.dtype), w0, u0)


_server_step = jax.jit(_server_update, static_argnames=("v",))
# the same, writing the new w0 over the old one where no one keeps it
_server_step_into = jax.jit(_server_update, static_argnames=("v",),
                            donate_argnums=(0,))


def round_core(w0, stale, fresh, step_key, tokens, targets, m_t, *, m, v,
               operands, rows, consume=False):
    """One round after the schedule's draws: returns (h, coefficient of
    the party, new fresh block of party m_t, coefficient of the server,
    new w0). ``stale`` is the (q, ...) party tree each party's c comes
    from; ``fresh`` is party m_t's current block. With ``consume`` the
    new w0 takes the old one's buffers."""
    kw = dict(m=m, v=v, operands=operands, rows=rows)
    h, coeff, new_fresh, cs = _party_round(w0, stale, fresh, step_key,
                                           tokens, targets, m_t, **kw)
    h_hat = _h_hat(w0, cs, step_key, targets, **kw)
    coeff0 = (h_hat - h) / dict(v)["mu"]
    update = _server_step_into if consume else _server_step
    return h, coeff, new_fresh, coeff0, update(w0, step_key, coeff0, v=v)


def run(w0, parties, key, batches, m: dict, v: dict, operands="f32",
        fault=None):
    """Follow the program's first ``len(batches)`` rounds from the start
    state (w0, stacked parties, state key). Returns the losses, the
    coefficients, the activated parties and the (w0, parties) after the
    first round and after the last. ``w0`` is consumed: each round but
    the second writes its new w0 over the old (the state after the first
    round is kept), so that the check's other copies fit beside it."""
    q, tau = v["num_parties"], v["max_delay"]
    B = batches[0]["tokens"].shape[0]
    rows = {None: None, "half_batch": B // 2, "one_shard": B // 4}[fault]
    hist = [parties] * (tau + 1)
    out = {"h": [], "coeff": [], "coeff0": [], "m": [], "states": []}
    # hashable: scalars kept, a nested group (rope_scaling) as its items
    fz = lambda d: tuple(sorted(  # noqa: E731
        (k, tuple(sorted(x.items())) if isinstance(x, dict) else x)
        for k, x in d.items()
        if isinstance(x, (int, float, str, bool, type(None), dict))))
    for step, batch in enumerate(batches):
        m_t, delays = draws(key, step, q, tau)
        m_t, delays = int(m_t), np.asarray(delays)
        slots = (step - 1 - delays) % (tau + 1)
        stale = jax.tree.map(lambda *xs: jnp.stack(xs), *[
            jax.tree.map(lambda a, j=j: a[j], hist[slots[j]])
            for j in range(q)])
        fresh = jax.tree.map(lambda a: a[m_t], parties)
        h, coeff, new_fresh, coeff0, w0 = round_core(
            w0, stale, fresh, jax.random.fold_in(key, step),
            batch["tokens"], batch["targets"], m_t, m=fz(m), v=fz(v),
            operands=operands, rows=rows, consume=step != 1)
        parties = jax.tree.map(lambda a, b: a.at[m_t].set(b), parties,
                               new_fresh)
        hist[step % (tau + 1)] = parties
        out["h"].append(float(h))
        out["coeff"].append(float(coeff))
        out["coeff0"].append(float(coeff0))
        out["m"].append(m_t)
        if step in (0, len(batches) - 1):
            out["states"].append((w0, parties))
    return out
