"""The comparison's own arithmetic (chipbench/check.py) on small arrays."""
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check as chk


def _update(w, u, coeff):
    """w - lr * coeff * u rounded to w's dtype, as both sides do it."""
    return (w.astype(jnp.float32) - 0.05 * coeff * u).astype(w.dtype)


@pytest.mark.parametrize("coeff_prog,coeff_ref,fresh_dir,want", [
    (1.0, 1.0, False, 0.0),      # the same update
    (0.3, 2.0, False, 0.0),      # another coefficient, same direction
    (-1.5, 0.7, False, 0.0),     # the coefficient's sign is not held
    (0.0, 1.0, False, 1.0),      # a coefficient of 0: nothing moved
    (1.0, 1.0, True, 0.5),       # another direction: half against
])
def test_sign_gap(coeff_prog, coeff_ref, fresh_dir, want):
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(0, 0.02, 200_000), jnp.bfloat16)
    u = jnp.asarray(rng.normal(size=w.shape), jnp.float32)
    v = (jnp.asarray(rng.normal(size=w.shape), jnp.float32) if fresh_dir
         else u)
    prog = {"w0/a": np.asarray(_update(w, v, coeff_prog))}
    ref = {"w0/a": _update(w, u, coeff_ref)}
    got = chk.sign_gap(prog, ref, {"w0/a": w})
    assert got == pytest.approx(want, abs=0.02)


def test_loss_gap():
    assert chk.loss_gap([10.0, 10.001, 9.998], [10.0] * 3) == pytest.approx(
        2e-4)


@pytest.mark.parametrize("coeff", [2.5, -0.3, 0.0])
def test_along_reads_the_coefficient(coeff):
    """A change of -lr * coeff * u, stored in bf16, read along u over -lr
    gives coeff back, a state left on the host as one on the device."""
    rng = np.random.default_rng(1)
    start = {"w0/a": jnp.asarray(rng.normal(0, 0.02, 100_000), jnp.bfloat16),
             "party0/b": (0, jnp.asarray(rng.normal(0, 0.02, (2, 3000)),
                                         jnp.float32))}
    u = {"w0/a": jnp.asarray(rng.normal(size=100_000), jnp.float32),
         "party0/b": jnp.asarray(rng.normal(size=3000), jnp.float32)}
    last = {"w0/a": np.asarray(_update(start["w0/a"], u["w0/a"], coeff)),
            "party0/b": start["party0/b"][1][0] - 0.05 * coeff * u["party0/b"]}
    got = chk.along(last, start, u) / -0.05
    assert got == pytest.approx(coeff, abs=0.02)
