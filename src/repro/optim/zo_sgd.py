"""Zeroth-order SGD over a whole pytree — the centralized (NonF) training
path and the building block the AsyREVEL party update specializes.

The two-point round (perturb -> coefficient -> seed-replay apply) is the
same core/exchange.py ZOExchange the VFL trainers use; this module is the
degenerate single-party case where "the server" is the local loss_fn and
nothing crosses a wire. Supports multi-sample direction averaging
(variance reduction the paper points to via Liu et al. 2018) and
seed-replay (no materialized u).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.exchange import ZOExchange


def zo_sgd_step(loss_fn, params, key, lr: float, mu: float,
                dist: str = "gaussian", num_directions: int = 1,
                ex: ZOExchange | None = None):
    """params <- params - lr * mean_k coeff_k u_k. Returns (params, loss).

    ``ex`` injects a pre-built exchange (e.g. a DP-defended one from
    ``repro.dp``) in place of the default; the centralized path has no
    wire crossing, so a defended exchange only matters when the caller
    also routes payloads through ``ex.encode_up``/``roundtrip_up`` —
    passing it here keeps ONE exchange object across both uses."""
    if ex is None:
        ex = ZOExchange(mu=mu, direction=dist, num_directions=num_directions)
    f0 = loss_fn(params)

    def one(k):
        pert, _ = ex.perturb(params, k)
        return ex.coefficient(loss_fn(pert), f0)

    keys = jax.random.split(key, num_directions)
    coeffs = jax.vmap(one)(keys) if num_directions > 1 else \
        jnp.stack([one(keys[0])])
    # seed-replay accumulate (u regenerated; never stored across directions)
    new = params
    for i in range(num_directions):
        new = ex.apply_from_seed(new, keys[i], coeffs[i] / num_directions,
                                 lr)
    return new, f0
