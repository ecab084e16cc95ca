"""Properties of the two-point ZO estimator (paper Eqs. 14-17, Lemma 1/3),
with hypothesis over dimensions/smoothing/seeds."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import zoo
from repro.utils.prng import sample_direction

jax.config.update("jax_enable_x64", False)


@pytest.mark.slow
@settings(max_examples=20, deadline=None)
@given(d=st.integers(2, 200), seed=st.integers(0, 2**31 - 1),
       dist=st.sampled_from(["gaussian", "uniform"]))
def test_direction_second_moment_identity(d, seed, dist):
    """Our normalization makes E[u u^T] = I for BOTH laws, so the 1/mu
    prefactor is shared (zoo.py docstring)."""
    key = jax.random.key(seed)
    n = 4000
    us = jax.vmap(lambda k: sample_direction(k, (d,), dist))(
        jax.random.split(key, n))
    second = np.asarray(jnp.mean(jnp.square(us)))  # mean diag of uu^T
    assert abs(second - 1.0) < 0.15


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       dist=st.sampled_from(["gaussian", "uniform"]))
def test_uniform_direction_norm_is_sqrt_d(seed, dist):
    d = 64
    u = sample_direction(jax.random.key(seed), (d,), dist)
    n = float(jnp.linalg.norm(u))
    if dist == "uniform":
        assert abs(n - np.sqrt(d)) < 1e-3          # exactly on the sphere
    else:
        assert 0.4 * np.sqrt(d) < n < 2.0 * np.sqrt(d)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), mu=st.sampled_from([1e-4, 1e-3]),
       dist=st.sampled_from(["gaussian", "uniform"]))
def test_estimator_unbiased_for_linear_f(seed, mu, dist):
    """For linear f(w)=g.w the two-point estimate is coeff*u with
    coeff = g.u exactly, so E[grad_hat] = E[u u^T] g = g."""
    d = 32
    key = jax.random.key(seed)
    g = jax.random.normal(jax.random.fold_in(key, 1), (d,))
    w = jax.random.normal(jax.random.fold_in(key, 2), (d,))

    def f(x):
        return jnp.dot(g, x)

    n = 6000
    def one(k):
        pert, u = zoo.perturb(w, k, mu, dist)
        coeff = zoo.zo_coefficient(f(pert), f(w), mu)
        return zoo.zo_gradient(u, coeff)
    est = jax.vmap(one)(jax.random.split(key, n))
    mean = jnp.mean(est, axis=0)
    err = float(jnp.linalg.norm(mean - g) / jnp.linalg.norm(g))
    assert err < 0.25, err


def test_estimator_approximates_gradient_quadratic():
    """E[grad_hat] -> grad f_mu ~ grad f for small mu on a quadratic."""
    d = 16
    key = jax.random.key(0)
    A = jax.random.normal(jax.random.fold_in(key, 1), (d, d)) / np.sqrt(d)
    H = A @ A.T + jnp.eye(d)
    w = jax.random.normal(jax.random.fold_in(key, 2), (d,))

    def f(x):
        return 0.5 * jnp.dot(x, H @ x)

    grad_true = H @ w
    mu = 1e-4
    n = 20000
    def one(k):
        pert, u = zoo.perturb(w, k, mu, "gaussian")
        return zoo.zo_gradient(u, zoo.zo_coefficient(f(pert), f(w), mu))
    est = jnp.mean(jax.vmap(one)(jax.random.split(key, n)), axis=0)
    err = float(jnp.linalg.norm(est - grad_true)
                / jnp.linalg.norm(grad_true))
    assert err < 0.2, err


@pytest.mark.parametrize("dist", ["gaussian", "uniform", "rademacher"])
@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_seed_replay_equals_materialized(dist, seed):
    """The seed-regenerated apply must reproduce the update from
    perturb()'s materialized direction exactly — the MeZO-style memory
    optimization changes nothing numerically, for every law."""
    from repro.core.exchange import ZOExchange
    ex = ZOExchange(mu=1e-3, direction=dist)
    key = jax.random.key(seed)
    tree = {"a": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones((4,))}
    _, u = ex.perturb(tree, key)
    coeff = jnp.float32(2.5)
    g1 = ex.apply_direction(tree, u, coeff, 0.1)
    g2 = ex.apply_from_seed(tree, key, coeff, 0.1)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_apply_zo_update_matches_manual():
    key = jax.random.key(7)
    tree = {"w": jnp.ones((8,))}
    new = zoo.apply_zo_update(tree, key, "uniform", coeff=3.0, lr=0.1)
    u = zoo.direction_tree(key, tree, "uniform")
    expect = tree["w"] - 0.1 * 3.0 * u["w"]
    np.testing.assert_allclose(np.asarray(new["w"]), np.asarray(expect),
                               rtol=1e-6)


def test_smoothed_objective_close_to_f():
    """|f_mu - f| = O(mu^2) (Lemma 1.2 / 3.2)."""
    def f(w):
        return jnp.sum(jnp.sin(w["x"]))
    w = {"x": jnp.linspace(0, 1, 10)}
    for mu, tol in [(1e-2, 1e-3), (1e-1, 1e-1)]:
        fmu = zoo.gaussian_smoothed(f, jax.random.key(0), mu, "gaussian",
                                    num=4000)(w)
        assert abs(float(fmu - f(w))) < tol
