"""Seeded weights and inputs, made by the benchmark and not by the program.

Every value is a pure function of (seed, leaf path, layer, element index):
a 32-bit integer hash of the element index, salted per leaf and layer, mapped
to a uniform number in [-1, 1).  So the program's stacked parameters (made on
the device in one jitted call) and the reference's per-layer weights (made
again after the window, one layer at a time) hold the same numbers without
the reference taking anything the program holds.

Scales follow the program's own initialiser: matrices have the standard
deviation ``fan_in ** -0.5``, the embedding 0.02, norm weights lie in
[0.9, 1.1) so that a norm weight that is dropped or misapplied shows.
"""
from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

_SQRT3 = math.sqrt(3.0)


def _mix32(x):
    """murmur3's 32-bit finaliser on uint32 arrays (wrapping arithmetic)."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _mix32_host(x: int) -> int:
    m = 0xFFFFFFFF
    x &= m
    x ^= x >> 16
    x = (x * 0x85EBCA6B) & m
    x ^= x >> 13
    x = (x * 0xC2B2AE35) & m
    return x ^ (x >> 16)


def salt(seed: int, path: str, layer: int = 0) -> int:
    """uint32 salt of one leaf (and layer) under one seed.  ``seed`` may be
    any non-negative integer: its high and low 32-bit halves both count."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    h = _mix32_host(zlib.crc32(path.encode()) ^ 0x9E3779B9)
    while True:
        h = _mix32_host(h ^ (seed & 0xFFFFFFFF))
        seed >>= 32
        if not seed:
            break
    return _mix32_host(h + 0x632BE5AB * (layer + 1))


def uniform(shape, salt_u32) -> jax.Array:
    """float32 values in [-1, 1) of ``shape``; ``salt_u32`` is a traced
    uint32 scalar, so one compiled program serves every seed."""
    n = int(np.prod(shape)) if len(shape) else 1
    i = jax.lax.iota(jnp.uint32, n)
    x = _mix32(i * jnp.uint32(0x9E3779B1) + salt_u32)
    x = _mix32(x ^ salt_u32)
    u = (x >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -23) - 1.0
    return u.reshape(shape)


def leaf_values(path: str, shape, salt_u32) -> jax.Array:
    """The float32 values of one (per-layer) leaf before its cast."""
    u = uniform(shape, salt_u32)
    name = path.rsplit("/", 1)[-1]
    if "norm" in name:
        return 1.0 + 0.1 * u
    if name == "embed":
        return u * jnp.float32(0.02 * _SQRT3)
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    return u * jnp.float32(_SQRT3 / math.sqrt(fan_in))


def _paths(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for kp, leaf in flat:
        parts = []
        for k in kp:
            parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
        out.append(("/".join(parts), leaf))
    return out, treedef


def stacked(path: str) -> bool:
    """Leaves under ``blocks`` carry the layer axis first."""
    return path.startswith("blocks/")


def make_params(shapes, seed: int):
    """The program's parameter pytree (``shapes``: its ``jax.eval_shape``),
    filled from ``seed`` on the device in one jitted call."""
    items, treedef = _paths(shapes)
    salts = []
    for path, leaf in items:
        layers = leaf.shape[0] if stacked(path) else 1
        salts.append(np.asarray([salt(seed, path, l) for l in range(layers)],
                                np.uint32))
    return _build(tuple((p, tuple(l.shape), jnp.dtype(l.dtype).name)
                        for p, l in items), treedef)(salts)


@functools.lru_cache(maxsize=None)
def _build(spec, treedef):
    """One jitted program that makes every leaf of ``spec`` from its salts."""
    def fn(salts):
        leaves = []
        for (path, shape, dtype), s in zip(spec, salts):
            if stacked(path):
                v = jax.vmap(lambda si, shape=shape[1:], path=path:
                             leaf_values(path, shape, si))(s)
            else:
                v = leaf_values(path, shape, s[0])
            leaves.append(v.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _leaf_fn(path: str, shape: tuple, dtype: str):
    return jax.jit(lambda s: leaf_values(path, shape, s).astype(dtype))


def leaf(seed: int, path: str, shape, dtype, layer: int = 0) -> jax.Array:
    """One leaf (one layer of a stacked leaf) as the program holds it, made
    again from the seed: ``leaf_values`` cast to ``dtype``."""
    return _leaf_fn(path, tuple(shape), dtype)(
        jnp.uint32(salt(seed, path, layer)))
