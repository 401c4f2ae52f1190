"""Seeded weights for a cell, made by the benchmark in one jitted call on
the device, in the dtype the program stores them in.

The program supplies only the tree's shapes and leaf names (its parameter
specs); every value comes from here, so the reference, which reads the
same arrays, takes nothing that the program has made.

Butterfly stage weights are the FJLT's Hadamard stages with a seeded 10%
multiplicative jitter and a random sign diagonal: exact Hadamard stages tie
whole groups of logits, and a tie makes greedy tokens a matter of
rounding.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

JITTER = 0.1


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _stage_weights(key, shape, dtype):
    """(..., p, 2, n) jittered Hadamard stages with a sign diagonal on
    stage 0: ``a_s[i] = ±1/sqrt2`` by bit s of i, ``b_s[i] = 1/sqrt2``."""
    *lead, p, two, n = shape
    k1, k2 = jax.random.split(key)
    idx = jnp.arange(n)
    bits = (idx[None, :] >> jnp.arange(p)[:, None]) & 1          # (p, n)
    a = (1.0 - 2.0 * bits) / math.sqrt(2.0)
    b = jnp.full((p, n), 1.0 / math.sqrt(2.0))
    w = jnp.stack([a, b], axis=1)                                # (p, 2, n)
    w = jnp.broadcast_to(w, shape)
    w = w * (1.0 + JITTER * jax.random.normal(k1, shape))
    d = jax.random.rademacher(k2, tuple(lead) + (n,), jnp.float32)
    d_partner = jnp.take(d, idx ^ 1, axis=-1)
    stage0 = jnp.stack([w[..., 0, 0, :] * d, w[..., 0, 1, :] * d_partner],
                       axis=-2)
    w = w.at[..., 0, :, :].set(stage0)
    return w.astype(dtype)


def _fan_in(name: str, shape) -> int:
    if name == "w":                     # (..., n_in, n_out)
        return shape[-2]
    if name in ("wq", "wk", "wv"):      # (..., E, heads, D)
        return shape[-3]
    if name == "wo":                    # (..., H, D, E)
        return shape[-3] * shape[-2]
    if name == "table":                 # (V, E), scaled by sqrt(E) in use
        return shape[-1]
    raise KeyError(name)


def _leaf(key, name: str, shape, dtype, fan_in: int = 0):
    if name in ("b_in", "b_out"):
        return _stage_weights(key, shape, dtype)
    if len(shape) >= 1 and name in ("norm1", "norm2", "final_norm",
                                    "scale"):
        return (1.0 + 0.1 * jax.random.normal(key, shape)).astype(dtype)
    std = 1.0 / math.sqrt(fan_in or _fan_in(name, shape))
    return (std * jax.random.normal(key, shape)).astype(dtype)


def _leaf_name(path) -> str:
    """The last dict key on a leaf's path ('w', 'core', 'norm1', ...)."""
    for p in reversed(path):
        if hasattr(p, "key"):
            return str(p.key)
    raise ValueError(f"leaf path {path} has no dict key")


def make(shapes, seed: int, device=None):
    """Weights for the tree ``shapes`` (ShapeDtypeStructs), from ``seed``,
    in one jitted call; committed to ``device`` when given."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_leaf_name(p) for p, _ in flat]
    specs = [(tuple(s.shape), jnp.dtype(s.dtype)) for _, s in flat]
    # a sandwich's core is drawn with the padded input width as its fan-in
    # (the width of its input butterfly, ``b_in``), which gives the
    # sandwich the unit gain of a dense layer drawn with 1/sqrt(fan-in):
    # the truncation's sqrt(N/k) scalings and the two butterflies cancel
    b_in = {tuple(p[:-1]): s.shape[-1] for (p, s), n in zip(flat, names)
            if n == "b_in"}
    fans = [b_in[tuple(p[:-1])] if n == "core" else 0
            for (p, _), n in zip(flat, names)]

    def build(key):
        keys = jax.random.split(key, len(specs))
        return [_leaf(k, n, shape, dt, f) for k, n, (shape, dt), f
                in zip(keys, names, specs, fans)]

    kw = {} if device is None else {
        "out_shardings": jax.sharding.SingleDeviceSharding(device)}
    leaves = jax.jit(build, **kw)(seed_key(seed))
    return jax.tree_util.tree_unflatten(treedef, leaves)
