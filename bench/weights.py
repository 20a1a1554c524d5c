"""Seeded random weights for a configuration file, made on the device in
one jitted call, in the program's parameter layout.

The layout is derived here from the configuration's sizes, not read from
the program; `check_layout` holds the program's own `init` shapes to it,
so a run fails where the two part.  The plain reference builds its weights
with the same call, so it takes nothing the program has made.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def prng_key(seed: int) -> jax.Array:
    """A key from any non-negative whole number (seeds may exceed 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _norm(cfg) -> dict:
    d = (cfg["d_model"],)
    return {"scale": d, "bias": d} if cfg["norm"] == "layer" else {"scale": d}


def _dense(i: int, o: int, bias: bool) -> dict:
    return {"kernel": (i, o), "bias": (o,)} if bias else {"kernel": (i, o)}


def _layer(cfg) -> dict:
    d, f = cfg["d_model"], cfg["d_ff"]
    hd = cfg["num_heads"] * cfg["head_dim"]
    kvd = cfg["num_kv_heads"] * cfg["head_dim"]
    qb, mb = cfg["qkv_bias"], cfg["mlp_bias"]
    attn = {"wq": _dense(d, hd, qb), "wk": _dense(d, kvd, qb),
            "wv": _dense(d, kvd, qb), "wo": _dense(hd, d, False)}
    if cfg["act"] == "swiglu":
        mlp = {"wi_gate": _dense(d, f, mb), "wi_up": _dense(d, f, mb),
               "wo": _dense(f, d, mb)}
    else:
        mlp = {"wi": _dense(d, f, mb), "wo": _dense(f, d, mb)}
    return {"attn_norm": _norm(cfg), "attn": attn, "ffn_norm": _norm(cfg),
            "mlp": mlp}


def segments(cfg) -> list[tuple]:
    """[("run", first_layer, count) | ("memory", layer)] in layer order."""
    out, start = [], 0
    mem = cfg["memory_layer"]
    for i in range(cfg["num_layers"] + 1):
        if i == mem or i == cfg["num_layers"]:
            if i > start:
                out.append(("run", start, i - start))
            if i == mem:
                out.append(("memory", i))
            start = i + 1
    return out


def layout(cfg) -> tuple[dict, dict]:
    """(params, state) as nested dicts of shape tuples."""
    d, v = cfg["d_model"], cfg["vocab_size"]
    lr = cfg["lram"]
    params: dict = {"embed": {"embedding": (v, d)}, "final_norm": _norm(cfg)}
    if cfg["pos_scheme"] == "learned":
        params["pos_embed"] = (cfg["max_seq"], d)
    params["lm_head"] = {"kernel": (d, v)}
    segs, state = {}, {}
    for si, seg in enumerate(segments(cfg)):
        if seg[0] == "run":
            segs[f"seg{si}"] = jax.tree.map(
                lambda s, n=seg[2]: (n,) + s, _layer(cfg),
                is_leaf=lambda x: isinstance(x, tuple))
        else:
            q = 2 * 8  # real and imaginary parts of 8 complex coordinates
            layer = _layer(cfg)
            del layer["mlp"]
            layer["memffn"] = {
                "wi": _dense(d, d, True),
                "lram": {"values": (2 ** lr["log2_locations"], lr["m"]),
                         "qnorm": {"scale": (q,), "bias": (q,)}},
                "wo": _dense(lr["heads"] * lr["m"], d, True),
            }
            segs[f"seg{si}"] = layer
            state[f"seg{si}"] = {"lram": {"qnorm": {"mean": (q,),
                                                   "var": (q,)}}}
    params["segments"] = segs
    return params, state


def _leaf_init(cfg, path: str, shape: tuple, key) -> jax.Array:
    leaf = path.rsplit("/", 1)[-1]
    normal = jax.random.normal(key, shape, jnp.float32)
    if leaf == "embedding":
        return normal
    if leaf == "values":
        return cfg["lram"]["value_init_std"] * normal
    if leaf == "pos_embed":
        return 0.02 * normal
    if leaf == "kernel":
        return normal / np.sqrt(shape[-2])
    if leaf == "scale":
        return 1.0 + 0.1 * normal
    if leaf == "bias":
        is_norm = "norm" in path.rsplit("/", 2)[-2]
        return (0.1 if is_norm else 0.02) * normal
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
