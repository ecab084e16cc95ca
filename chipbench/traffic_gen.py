"""The one generator of the benchmark's inputs, read from a traffic file
and the seed.

``lm_table`` is a copy of the program's ``data.synthetic.make_lm_dataset``
(Markov-ish bigram token streams; targets are the tokens shifted by
one). ``classification_table`` has the semantics of the program's
``data.synthetic.make_classification`` (Gaussian rows, a random linear
teacher, 5% of labels flipped), but is made on the device in row blocks
inside one jitted loop, so that set-up never holds more than the table
and one block.
"""
from __future__ import annotations

import numpy as np


def lm_table(n: int, seq_len: int, vocab: int, seed: int):
    """(tokens, targets), each (n, seq_len) int32, on the host."""
    rng = np.random.default_rng(seed)
    trans = rng.integers(0, vocab, size=(vocab,))
    toks = np.empty((n, seq_len), np.int32)
    toks[:, 0] = rng.integers(0, vocab, size=n)
    for t in range(1, seq_len):
        follow = rng.random(n) < 0.7
        toks[:, t] = np.where(follow, trans[toks[:, t - 1]],
                              rng.integers(0, vocab, size=n))
    targets = np.roll(toks, -1, axis=1)
    return toks, targets


def classification_table(key, n: int, d: int, block: int,
                         noise: float = 0.05):
    """{"x": (n, d) f32, "y": (n,) f32 in {-1, 1}} on the default device."""
    import jax
    import jax.numpy as jnp

    assert n % block == 0, "rows must be a whole number of blocks"

    def make(key):
        k_w, k_rows = jax.random.split(key)
        w = jax.random.normal(k_w, (d,), jnp.float32) / np.sqrt(d)

        def body(i, xy):
            x, y = xy
            k = jax.random.fold_in(k_rows, i)
            k_x, k_f = jax.random.split(k)
            xb = jax.random.normal(k_x, (block, d), jnp.float32)
            yb = jnp.sign(jnp.dot(xb, w, precision="highest") + 1e-9)
            flip = jax.random.uniform(k_f, (block,)) < noise
            yb = jnp.where(flip, -yb, yb)
            x = jax.lax.dynamic_update_slice_in_dim(x, xb, i * block, 0)
            y = jax.lax.dynamic_update_slice_in_dim(y, yb, i * block, 0)
            return x, y

        x0 = jnp.zeros((n, d), jnp.float32)
        y0 = jnp.zeros((n,), jnp.float32)
        x, y = jax.lax.fori_loop(0, n // block, body, (x0, y0))
        return {"x": x, "y": y}

    return jax.jit(make)(key)
