"""The comparison that decides ``correct``: the program's readings against
the reference's, each number beside its limit.

Leaf-wise numbers are taken leaf by leaf, never as one norm over the
whole state. A party's block of a stacked party leaf counts as a leaf of
its own. A change is a state minus the start state.

- ``loss_gap``: the largest |h_prog - h_ref| / |h_ref| over the rounds
  the reference follows.
- ``grad_gap``: the first gradient as the optimizer got it, worked out
  from the state after one round (its change divided by the learning
  rate, which the ratio cancels): by the worst leaf,
  | ||d_prog|| - ||d_ref|| | / max(||d_ref||, median leaf's ||d_ref||).
- ``change_gap``: the same measure on the change after the last round
  the reference follows.
- ``change_median``: the median leaf's gap of the same kind as
  ``change_gap``: steadier from seed to seed than the worst leaf's.
- ``value_gap``: the largest gap, in loss per mu, among the values a
  zeroth-order round is computed from: the first round's loss over mu,
  and each round's server and party coefficients (a difference of two
  losses over mu) as the state after the last round shows them, its
  change from the start along the round's direction over -lr. Absolute,
  since a coefficient near 0 is as noisy as a large one; each gap is
  the rounding of the losses. A later round's loss is left out here: it
  follows the earlier rounds' updates, whose coefficients already differ
  by that rounding (``loss_gap`` holds it, against a wider limit).
- ``dir_gap``: 1 - |cos(d_prog, d_ref)| of the f32 party block's change,
  its leaves taken as one vector: the first round's activated party for
  the vfl-zoo step, every leaf after the whole call for the scan. 0
  where the program moved the block along the reference's direction
  (either way: a coefficient's sign is as noisy as its size), 1 where it
  left it unmoved. The bf16 server leaves are left out: an update near
  half their ulp moves a few elements on one side and others on the
  other, whatever the program does.
- ``w0_sign_gap``: the server's update after the first round, taken
  element by element over every w0 leaf. Of the elements that both the
  program and the reference moved, the share that moved against the
  majority's sense: min(opposite, same) / both. Both sides round
  w - lr * coeff0 * u0 to bf16 with the same direction u0, and rounding
  is monotone, so an update along u0 with any coefficient reads 0 and
  one along another direction about 0.5. 1 where no element moved on
  both sides: an update dropped or a coefficient of 0. The coefficient's
  size and sign are not held: at bf16 the two forwards' rounding is
  most of h_hat - h (PERF.md).

A leaf counts only where the reference's change is at least a
thousandth of the median leaf's: a leaf the reference leaves unmoved
(a bf16 norm scale that an update below half its ulp cannot move) says
nothing about the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

TINY = 1e-3


def named_leaves(w0, parties, q: int, only_party: int | None = None):
    """{name: array} over the server tree and each party's block."""
    from chipbench.weights import path_name
    out = {}
    if w0 is not None:
        for path, a in jax.tree_util.tree_flatten_with_path(w0)[0]:
            out["w0/" + path_name(path)] = a
    for path, a in jax.tree_util.tree_flatten_with_path(parties)[0]:
        for j in range(q):
            if only_party is None or j == only_party:
                out[f"party{j}/" + path_name(path)] = (j, a)
    return out


@jax.jit
def _stats(p, r, s):
    s = s.astype(jnp.float32)
    dp, dr = p.astype(jnp.float32) - s, r.astype(jnp.float32) - s
    return jnp.stack([jnp.sum(dp * dp), jnp.sum(dr * dr), jnp.sum(dp * dr)])


def _pick(a):
    if isinstance(a, tuple):
        j, arr = a
        return arr[j]
    return a


def leaf_stats(prog: dict, ref: dict, start: dict) -> dict:
    """{name: (||d_prog||, ||d_ref||, cos)} for the names in ``ref``."""
    out = {}
    for name in ref:
        r, s = _pick(ref[name]), _pick(start[name])
        p = jax.device_put(np.asarray(_pick(prog[name])),
                           next(iter(r.devices())))
        pp, rr, pr = (float(x) for x in np.asarray(_stats(p, r, s),
                                                  np.float64))
        np_, nr = np.sqrt(pp), np.sqrt(rr)
        cos = pr / (np_ * nr) if np_ > 0 and nr > 0 else 0.0
        out[name] = (float(np_), float(nr), float(cos))
    return out


def counted(stats: dict) -> tuple[dict, float]:
    med = float(np.median([nr for _, nr, _ in stats.values()]))
    keep = {k: v for k, v in stats.items() if v[1] >= TINY * med and v[1] > 0}
    return keep, med


def norm_gap(stats: dict) -> tuple[float, str]:
    keep, med = counted(stats)
    worst, name = 0.0, ""
    for k, (np_, nr, _) in keep.items():
        g = abs(np_ - nr) / max(nr, med)
        if g >= worst:
            worst, name = g, k
    return worst, name


def dir_gap(stats: dict) -> tuple[float, str]:
    keep, _ = counted(stats)
    worst, name = 0.0, ""
    for k, (_, _, cos) in keep.items():
        g = 1.0 - abs(cos)
        if g >= worst:
            worst, name = g, k
    return worst, name


def block_dir_gap(stats: dict) -> float:
    """1 - |cos| between the program's and the reference's changes of the
    given leaves taken together as one vector."""
    dot = sum(c * np_ * nr for np_, nr, c in stats.values())
    pp = sum(np_ * np_ for np_, _, _ in stats.values())
    rr = sum(nr * nr for _, nr, _ in stats.values())
    if pp == 0 or rr == 0:
        return 1.0
    return 1.0 - abs(dot) / float(np.sqrt(pp * rr))


def median_gaps(stats: dict) -> tuple[float, float]:
    """(norm gap, direction gap) of the median counted leaf: steadier
    from seed to seed than the worst leaf's."""
    keep, med = counted(stats)
    norm = [abs(np_ - nr) / max(nr, med) for np_, nr, _ in keep.values()]
    cos = [1.0 - abs(c) for _, _, c in keep.values()]
    return float(np.median(norm)), float(np.median(cos))


def named(prefix: str, tree) -> dict:
    """{prefix + leaf path: leaf} over ``tree``."""
    from chipbench.weights import path_name
    return {prefix + path_name(path): a
            for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]}


@jax.jit
def _along(p, s, u):
    d = p.astype(jnp.float32) - s.astype(jnp.float32)
    return jnp.stack([jnp.sum(d * u), jnp.sum(u * u)])


def along(last: dict, start: dict, dirs: dict) -> float:
    """<d, u> / <u, u> of the change d from ``start`` to ``last`` along
    ``dirs`` ({name: f32 direction leaf}), its leaves taken together as
    one vector."""
    num = den = 0.0
    for name, u in dirs.items():
        p = _pick(last[name])
        if not isinstance(p, jax.Array):
            p = jax.device_put(np.asarray(p), next(iter(u.devices())))
        a, b = np.asarray(_along(p, _pick(start[name]), u), np.float64)
        num, den = num + float(a), den + float(b)
    return num / den


def loss_gap(h_prog, h_ref) -> float:
    h_prog, h_ref = np.asarray(h_prog, np.float64), np.asarray(h_ref,
                                                                np.float64)
    return float(np.max(np.abs(h_prog - h_ref) / np.abs(h_ref)))


@jax.jit
def _signs(p, r, s):
    # comparisons, not differences: exact in any dtype, no flush to zero
    dp = (p > s).astype(jnp.int8) - (p < s).astype(jnp.int8)
    dr = (r > s).astype(jnp.int8) - (r < s).astype(jnp.int8)
    both = (dp != 0) & (dr != 0)
    return jnp.stack([jnp.sum(both), jnp.sum(both & (dp != dr))])


def sign_gap(prog: dict, ref: dict, start: dict) -> float:
    """``w0_sign_gap`` over the names in ``ref`` (module docstring)."""
    both = opposite = 0
    for name in ref:
        r, s = _pick(ref[name]), _pick(start[name])
        p = jax.device_put(np.asarray(_pick(prog[name])),
                           next(iter(r.devices())))
        b, o = (int(x) for x in np.asarray(_signs(p, r, s)))
        both, opposite = both + b, opposite + o
    return min(opposite, both - opposite) / both if both else 1.0


def judge(readings: dict, limits: dict) -> tuple[bool, list]:
    """(correct, [(name, value, limit)]): every number with a limit must
    be finite and at most its limit; a number whose limit is null is
    printed beside it and not compared."""
    compared, ok = [], True
    for name, value in readings.items():
        limit = limits.get(name)
        compared.append((name, value, limit))
        if limit is not None and not (np.isfinite(value) and value <= limit):
            ok = False
    return ok, compared
