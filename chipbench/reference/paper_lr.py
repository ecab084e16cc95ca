"""Plain reference of the paper's black-box nonconvex logistic regression
(Eq. 22) under AsyREVEL (Algorithm 1), driven as the scan trainer drives
it: each round draws its batch rows from its own key, then activates one
party, gives the others parameters up to ``max_delay`` rounds stale, and
forms both two-point estimates. Imports nothing of the program; follows
its documented key schedule (see qwen2_zoo.py).

``operands`` "f32" computes each party's x_m @ w_m in f32 (HIGHEST);
"high" keeps three bf16 products of each (the split of every f32 operand
into a bf16 head and a bf16 tail, the tails' product dropped), the step
below the configuration's f32 at HIGHEST; "bf16" rounds both operands
to bfloat16 first (one pass, the chip's default precision). On a TPU v5e
"high" reads bit for bit as HIGHEST here, so "bf16", the nearest step
below that differs, is the control. ``fault`` "half_batch" takes every
loss over the first half of the rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.reference.qwen2_zoo import directions, fold_name

HI = jax.lax.Precision.HIGHEST
CONTROL = "bf16"
# read beside the control by calibrate.py
ALSO = ("high",)


def _dot(x, w, operands):
    if operands == "f32":
        return jnp.dot(x, w, precision=HI)
    xh = x.astype(jnp.bfloat16).astype(jnp.float32)
    if operands == "bf16":
        return jnp.dot(xh, w.astype(jnp.bfloat16).astype(jnp.float32),
                       precision=HI)
    wh = w.astype(jnp.bfloat16).astype(jnp.float32)
    xl = (x - xh).astype(jnp.bfloat16).astype(jnp.float32)
    wl = (w - wh).astype(jnp.bfloat16).astype(jnp.float32)
    return (jnp.dot(xh, wh, precision=HI) + jnp.dot(xh, wl, precision=HI)
            + jnp.dot(xl, wh, precision=HI))


def reg(w):
    return jnp.sum(w * w / (1.0 + w * w))


@functools.partial(jax.jit, static_argnames=("v", "operands", "rows"))
def follow(b, parties, key, keys, x_all, y_all, *, v, operands, rows):
    """Rounds 0 .. len(keys)-1 from the start state (b, parties (q, pad)).
    Returns (losses, b, parties)."""
    v = dict(v)
    q, tau, mu, lam = (v["num_parties"], v["max_delay"], v["mu"],
                       v["lam"])
    n, d = x_all.shape
    pad = d // q
    B = v["batch"]

    def loss(b, cs, y):
        z = jnp.sum(cs, axis=1) + b
        out = jnp.log1p(jnp.exp(-y * z))
        return jnp.mean(out if rows is None else out[:rows])

    def body(carry, inp):
        b, parties, hist = carry
        step, k = inp
        idx = jax.random.randint(k, (B,), 0, n)
        x, y = x_all[idx], y_all[idx]
        xs = x.reshape(B, q, pad)
        rk = jax.random.fold_in(key, step)
        m_t = jax.random.categorical(fold_name(rk, "party"),
                                     jnp.log(jnp.full((q,), 1.0 / q)))
        delays = jax.random.randint(fold_name(rk, "delay"), (q,), 0,
                                    tau + 1).at[m_t].set(0)
        slots = (step - 1 - delays) % (tau + 1)
        stale = hist[slots, jnp.arange(q)]                   # (q, pad)
        cs = jnp.stack([_dot(xs[:, j], stale[j], operands)
                        for j in range(q)], axis=1)
        w_m = parties[m_t]
        x_m = jax.lax.dynamic_index_in_dim(xs, m_t, 1, keepdims=False)
        h = loss(b, cs, y)
        u = directions(fold_name(rk, "u"), {"w": w_m})["w"]
        w_p = w_m + mu * u
        h_bar = loss(b, cs.at[:, m_t].set(_dot(x_m, w_p, operands)), y)
        coeff = ((h_bar + lam * reg(w_p)) - (h + lam * reg(w_m))) / mu
        parties = parties.at[m_t].set(w_m - v["lr_party"] * coeff * u)
        u0 = directions(fold_name(rk, "u0"), {"b": b})["b"]
        h_hat = loss(b + mu * u0, cs, y)
        b = b - v["lr_server"] * ((h_hat - h) / mu) * u0
        hist = hist.at[step % (tau + 1)].set(parties)
        return (b, parties, hist), h

    hist = jnp.broadcast_to(parties[None], (tau + 1,) + parties.shape)
    steps = jnp.arange(keys.shape[0])
    (b, parties, _), losses = jax.lax.scan(body, (b, parties, hist),
                                           (steps, keys))
    return losses, b, parties


def run(b, parties, key, keys, data, v: dict, operands="f32", fault=None):
    rows = {None: None, "half_batch": v["batch"] // 2}[fault]
    fz = tuple(sorted((k, x) for k, x in v.items()
                      if isinstance(x, (int, float, str, bool, type(None)))))
    losses, b, parties = follow(b, parties, key, keys, data["x"], data["y"],
                                v=fz, operands=operands, rows=rows)
    return {"h": [float(x) for x in losses], "state": (b, parties)}
