"""Weights made by the benchmark from the seed, on the device, in one
jitted call, in the dtype each leaf is stored in.

Each leaf's values depend only on the seed and the leaf's path, so the
program and the reference get the same numbers however their trees are
ordered. The law follows the published initializer (Qwen2's
``initializer_range``): norm scales are ones, biases zeros, every other
leaf normal with standard deviation ``std``.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def path_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "name", k)))
                    for k in path)


def leaf_kind(name: str) -> str:
    last = name.rsplit("/", 1)[-1]
    if "norm" in last or last.endswith("gamma"):
        return "ones"
    if last in ("bq", "bk", "bv", "b", "b1", "b2"):
        return "zeros"
    return "normal"


def fill(shapes, key, std: float, kinds: dict | None = None):
    """A tree shaped like ``shapes`` (ShapeDtypeStructs), made on the
    device in one jitted call. ``kinds`` overrides ``leaf_kind`` by leaf
    name, as {"name": ("normal", std) | "ones" | "zeros"}."""
    kinds = kinds or {}
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        out = []
        for path, s in flat:
            name = path_name(path)
            kind = kinds.get(name, leaf_kind(name))
            if kind == "ones":
                out.append(jnp.ones(s.shape, s.dtype))
            elif kind == "zeros":
                out.append(jnp.zeros(s.shape, s.dtype))
            else:
                sd = kind[1] if isinstance(kind, tuple) else std
                k = jax.random.fold_in(key, zlib.crc32(name.encode()))
                out.append((jax.random.normal(k, s.shape, jnp.float32)
                            * sd).astype(s.dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(make)(key)
