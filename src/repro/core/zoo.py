"""Zeroth-order two-point gradient estimation (paper Eqs. 14, 15, 17).

The paper writes the estimator as
    grad_hat_m f = (d_m / mu_m) [f(w_m + mu_m u) - f(w_m)] u ,
with u drawn from N(0,I) (AsyREVEL-Gau) or Unif(S^{d-1}) (AsyREVEL-Uni).
We normalize directions so that E[u u^T] = I in BOTH cases (the uniform
direction is scaled by sqrt(d); see utils/prng.sample_direction). Under this
convention the estimator is uniformly
    grad_hat_m f = (1 / mu_m) [f(w_m + mu_m u) - f(w_m)] u ,
which equals the paper's form up to its unit-norm-u bookkeeping and keeps the
Gau/Uni code path identical — the two algorithms differ only in the
direction law, exactly as in the paper.

Seed-replay (beyond-paper, MeZO-style): the direction u never needs to be
materialized in HBM — both the perturbation and the update regenerate it from
the same PRNG key. ``apply_zo_update`` is that path; ``leaf_keys`` is the one
per-leaf key split every draw uses, so the Pallas update kernel's tree
wrappers (kernels/fused_round) replay the same streams.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.utils.prng import sample_direction


def leaf_keys(key, tree):
    """One PRNG key per leaf of ``tree``, in flatten order."""
    return jax.random.split(key, len(jax.tree.leaves(tree)))


def direction_tree(key, tree, dist: str):
    """One direction leaf per parameter leaf, deterministically keyed."""
    leaves, treedef = jax.tree.flatten(tree)
    us = [sample_direction(k, leaf.shape, dist, jnp.float32)
          for k, leaf in zip(leaf_keys(key, tree), leaves)]
    return jax.tree.unflatten(treedef, us)


def perturb(tree, key, mu: float, dist: str):
    """w + mu * u. Returns (perturbed_tree, u_tree)."""
    u = direction_tree(key, tree, dist)
    pert = jax.tree.map(lambda w, d: w + mu * d.astype(w.dtype), tree, u)
    return pert, u


def zo_coefficient(f_plus, f_base, mu: float):
    """The scalar [f(w+mu u) - f(w)] / mu — the ONLY quantity that crosses
    the network in ZOO-VFL besides the function values themselves."""
    return (f_plus - f_base) / mu


def zo_gradient(u_tree, coeff):
    """grad_hat = coeff * u (Eq. 15 with normalized directions)."""
    return jax.tree.map(lambda u: coeff * u, u_tree)


def apply_zo_update(tree, key, dist: str, coeff, lr: float):
    """w <- w - lr * coeff * u(key), regenerating u on the fly (the
    seed-regenerated apply; the Pallas kernel's tree wrapper is
    kernels/fused_round.zo_apply)."""
    u = direction_tree(key, tree, dist)
    return jax.tree.map(
        lambda w, d: (w.astype(jnp.float32)
                      - lr * coeff * d).astype(w.dtype), tree, u)


def gaussian_smoothed(f, key, mu: float, dist: str, num: int = 64):
    """Monte-Carlo estimate of the smoothed objective f_mu (used by tests to
    check E[grad_hat] ~= grad f_mu, Lemma 1/3)."""
    def one(k, w):
        u = direction_tree(k, w, dist)
        wp = jax.tree.map(lambda a, d: a + mu * d, w, u)
        return f(wp)

    def fn(w):
        keys = jax.random.split(key, num)
        return jnp.mean(jax.vmap(lambda k: one(k, w))(keys))
    return fn
