"""Seeded random weights for a configuration file, made on the device in
one jitted call, in the program's parameter layout.

The layout is the configuration's reference module's
(`bench/reference/<reference>.py`, `layout`), derived from the
configuration's sizes and not read from the program; `check_layout` holds
the program's own `init` shapes to it, so a run fails where the two part.
Every leaf is initialised by its name or, for any other weight of rank 2
or more, by fan-in over its input axis (`shape[-2]`), so a new
architecture's leaves need no code here.  The plain reference builds its
weights with the same call, so it takes nothing the program has made.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

import harness


def prng_key(seed: int) -> jax.Array:
    """A key from any non-negative whole number (seeds may exceed 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def layout(cfg) -> tuple[dict, dict]:
    """(params, state) as nested dicts of shape tuples."""
    return harness.reference(cfg["reference"]).layout(cfg)


def _leaf_init(cfg, path: str, shape: tuple, key) -> jax.Array:
    leaf = path.rsplit("/", 1)[-1]
    normal = jax.random.normal(key, shape, jnp.float32)
    if leaf == "embedding":
        return normal
    if leaf == "values":
        return cfg["lram"]["value_init_std"] * normal
    if leaf == "pos_embed":
        return 0.02 * normal
    if leaf == "scale":
        return 1.0 + 0.1 * normal
    if leaf == "bias":
        is_norm = "norm" in path.rsplit("/", 2)[-2]
        return (0.1 if is_norm else 0.02) * normal
    if len(shape) >= 2:  # a kernel, a router, stacked expert weights
        return normal / np.sqrt(shape[-2])
    raise ValueError(f"no initializer for {path}")


def _paths(tree, prefix=""):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _paths(tree[k], f"{prefix}/{k}" if prefix else k)
    else:
        yield prefix, tree


def _set(tree, path, value):
    *head, last = path.split("/")
    for k in head:
        tree = tree.setdefault(k, {})
    tree[last] = value


def make(cfg, seed: int, dtype=None):
    """(params, state) on the default device from `seed`, in one jitted
    call.  `dtype` defaults to the configuration's."""
    dtype = jnp.dtype(dtype or cfg["dtype"])
    p_shapes, s_shapes = layout(cfg)
    leaves = list(_paths(p_shapes))

    @jax.jit
    def build(key):
        params: dict = {}
        for path, shape in leaves:
            k = jax.random.fold_in(key, zlib.crc32(path.encode()))
            _set(params, path, _leaf_init(cfg, path, shape, k).astype(dtype))
        state: dict = {}
        for path, shape in _paths(s_shapes):
            fill = jnp.ones if path.endswith("var") else jnp.zeros
            _set(state, path, fill(shape, jnp.float32))
        return params, state

    return build(prng_key(seed))


def check_layout(cfg, program_params, program_state) -> None:
    """Raise where the program's (params, state) shapes differ from the
    layout this file derives from the configuration."""
    want = layout(cfg)
    got = jax.tree.map(lambda x: tuple(x.shape), (program_params,
                                                  program_state))
    if got != want:
        raise ValueError("the program's parameter layout differs from the "
                         f"configuration's: {got} != {want}")
