"""Plain reference of one AsyREVEL round over a Qwen2-family server with
q party embedding towers (the vfl-zoo step), written from the published
model description and the paper's Algorithm 1. It imports nothing of the
program. It takes the benchmark's weights, batches and seed, and follows
the program's documented key schedule (each round's key is the state key
folded with the round number, and each draw folds in a name), since a
zeroth-order update is only defined together with its random directions.

Every matmul runs at ``precision`` (HIGHEST, f32 products) on operands
first rounded to ``operands``: "f32" for the reference, "fp8"
(float8_e4m3fn) for the control, one step below the configuration's
bfloat16. Stored parameters keep their dtypes: the server's bf16 leaves
are perturbed and updated with the rounding the configuration states.

``fault`` plants a fault in the reference put in the program's place:
"half_batch" (every loss a mean over the first half of the rows) or
"one_shard" (over the first quarter: a 4-way data-parallel step whose
losses were never averaged across chips).
"""
from __future__ import annotations

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
CONTROL = "fp8"
# read beside the control by calibrate.py: the reference at the
# configuration's own precision, the second witness of how much of the
# program's gap is rounding
ALSO = ("bf16",)
OPERANDS = {"f32": None, "bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}


def fold_name(key, name: str):
    h = int.from_bytes(hashlib.sha256(name.encode()).digest()[:4], "little")
    return jax.random.fold_in(key, h)


def _rd(x, operands):
    dt = OPERANDS[operands]
    x = x.astype(jnp.float32)
    return x if dt is None else x.astype(dt).astype(jnp.float32)


def _mm(spec, a, b, operands):
    return jnp.einsum(spec, _rd(a, operands), _rd(b, operands),
                      precision=HI, preferred_element_type=jnp.float32)


def _rms(x, g, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * g.astype(jnp.float32)


def _rope(x, theta):
    """x (B, S, H, hd); rotate-half convention of the published model."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def server_loss(w0, x, targets, m: dict, operands="f32", rows=None):
    """Token-mean cross-entropy of the server model on input embeddings
    x (B, S, d), over the first ``rows`` rows (all by default)."""
    if rows is not None:
        x, targets = x[:rows], targets[:rows]
    B, S, d = x.shape
    H, KV = m["num_attention_heads"], m["num_key_value_heads"]
    hd = d // H
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    mask = jnp.tril(jnp.ones((S, S), bool))
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731

    def layer(x, p):
        a = p["attn"]
        xn = _rms(x, p["norm1"], eps)
        q = _mm("bsd,de->bse", xn, a["wq"], operands) + f32(a["bq"])
        k = _mm("bsd,de->bse", xn, a["wk"], operands) + f32(a["bk"])
        v = _mm("bsd,de->bse", xn, a["wv"], operands) + f32(a["bv"])
        q = _rope(q.reshape(B, S, H, hd), theta)
        k = _rope(k.reshape(B, S, KV, hd), theta)
        v = v.reshape(B, S, KV, hd)
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
        s = _mm("bqhe,bkhe->bhqk", q, k, operands) / np.sqrt(hd)
        s = jnp.where(mask[None, None], s, -jnp.inf)
        o = _mm("bhqk,bkhe->bqhe", jax.nn.softmax(s, axis=-1), v, operands)
        x = x + _mm("bse,ed->bsd", o.reshape(B, S, H * hd), a["wo"],
                    operands)
        mlp = p["mlp"]
        xn = _rms(x, p["norm2"], eps)
        g = _mm("bsd,df->bsf", xn, mlp["w_gate"], operands)
        u = _mm("bsd,df->bsf", xn, mlp["w_up"], operands)
        x = x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, mlp["w_down"],
                    operands)
        return x, None

    x, _ = jax.lax.scan(layer, x.astype(jnp.float32), w0["layers"])
    x = _rms(x, w0["final_norm"], eps)

    def row_loss(row):
        """One sequence's summed cross-entropy: the f32 logits of one row
        at a time, so that the head fits beside the weights."""
        xr, tr = row
        logits = _mm("sd,vd->sv", xr, w0["embed"], operands)
        logz = jax.nn.logsumexp(logits, axis=-1)
        return jnp.sum(logz - jnp.take_along_axis(logits, tr[:, None],
                                                  axis=-1)[:, 0])

    return jnp.sum(jax.lax.map(row_loss, (x, targets))) / (B * S)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi)
                                     * (x + 0.044715 * x ** 3)))


def party_out(p, tokens, operands="f32"):
    e = p["embed"][tokens].astype(jnp.float32)
    h = _gelu_tanh(_mm("bsd,dh->bsh", e, p["w1"], operands))
    return e + _mm("bsh,hd->bsd", h, p["w2"], operands)


def directions(key, tree):
    """One standard normal f32 leaf per leaf of ``tree``, in its
    flattened order, from ``split(key, number of leaves)``."""
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [
        jax.random.normal(k, a.shape, jnp.float32)
        for k, a in zip(keys, leaves)])


def draws(key, step, q: int, tau: int):
    """The activated party and the delays of round ``step``."""
    key = jax.random.fold_in(key, step)
    m_t = jax.random.categorical(fold_name(key, "party"),
                                 jnp.log(jnp.full((q,), 1.0 / q)))
    delays = jax.random.randint(fold_name(key, "delay"), (q,), 0, tau + 1)
    return m_t, delays.at[m_t].set(0)


@functools.partial(jax.jit, static_argnames=("m", "v", "operands", "rows"))
def round_core(w0, stale, fresh, step_key, tokens, targets, m_t, *, m, v,
               operands, rows):
    """One round after the schedule's draws: returns (h, coefficient of
    the party, new fresh block of party m_t, coefficient of the server,
    new w0). ``stale`` is the (q, ...) party tree each party's c comes
    from; ``fresh`` is party m_t's current block."""
    m, v = dict(m), dict(v)
    q = v["num_parties"]
    mu, lam = v["mu"], v["lam"]
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
    u0 = directions(fold_name(step_key, "u0"), w0)
    w0p = jax.tree.map(
        lambda w, d: w + (mu * d.astype(w.dtype)).astype(w.dtype), w0, u0)
    h_hat = loss(w0p, cs.reshape(B, S, -1))
    coeff0 = (h_hat - h) / mu
    new_w0 = jax.tree.map(
        lambda w, d: (w.astype(jnp.float32) - v["lr_server"] * coeff0 * d
                      ).astype(w.dtype), w0, u0)
    return h, coeff, new_fresh, coeff0, new_w0


def run(w0, parties, key, batches, m: dict, v: dict, operands="f32",
        fault=None):
    """Follow the program's first ``len(batches)`` rounds from the start
    state (w0, stacked parties, state key). Returns the losses, the
    coefficients, the activated parties and the (w0, parties) after the
    first round and after the last."""
    q, tau = v["num_parties"], v["max_delay"]
    B = batches[0]["tokens"].shape[0]
    rows = {None: None, "half_batch": B // 2, "one_shard": B // 4}[fault]
    hist = [parties] * (tau + 1)
    out = {"h": [], "coeff": [], "coeff0": [], "m": [], "states": []}
    fz = lambda d: tuple(sorted(  # noqa: E731
        (k, x) for k, x in d.items()
        if isinstance(x, (int, float, str, bool, type(None)))))
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
            operands=operands, rows=rows)
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
