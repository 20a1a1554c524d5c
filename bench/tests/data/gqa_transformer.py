"""Plain reference of a causal LM with grouped-query attention (keys and
values have `num_kv_heads` heads, each shared by `num_heads /
num_kv_heads` query heads, as Llama-family models such as Yi have them)
and the lattice memory FFN in one layer.

A test copies this file into a copy of the benchmark's `reference/`, to
show that a configuration of another architecture brings its reference
module as a new file: it takes `lram_transformer`'s mathematics and adds
what that lacks, and states its own layout and model FLOPs.
"""

from __future__ import annotations

import jax.numpy as jnp

import harness

lt = harness.reference("lram_transformer")


def attention(cfg, p, x):
    b, s, _ = x.shape
    h, kv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    q = lt.dense(cfg, p["wq"], x).reshape(b, s, h, hd)
    k = lt.dense(cfg, p["wk"], x).reshape(b, s, kv, hd)
    v = lt.dense(cfg, p["wv"], x).reshape(b, s, kv, hd)
    if cfg["pos_scheme"] == "rope":
        q, k = lt.rope(q, cfg["rope_theta"]), lt.rope(k, cfg["rope_theta"])
    # query head i reads key and value head i // (h / kv)
    k, v = jnp.repeat(k, h // kv, axis=2), jnp.repeat(v, h // kv, axis=2)
    return lt.dense(cfg, p["wo"], lt.softmax_attention(cfg, q, k, v))


def forward(cfg, params, state, tokens, *, train=False):
    return lt.forward(cfg, params, state, tokens, train=train,
                      attend=attention)


def train_step_fn(cfg):
    return lt.train_step_fn(cfg, forward=forward)


def layout(cfg):
    """The dense layout: `wk` and `wv` already have num_kv_heads heads."""
    return lt.layout(cfg)


def macs_per_token(cfg) -> int:
    """Per layer: q and output projections of num_heads heads, k and v of
    num_kv_heads; a SwiGLU MLP in every layer but the memory layer; the
    output head."""
    d, f, n = cfg["d_model"], cfg["d_ff"], cfg["num_layers"]
    q = cfg["num_heads"] * cfg["head_dim"]
    kv = cfg["num_kv_heads"] * cfg["head_dim"]
    return n * (2 * d * q + 2 * d * kv) + (n - 1) * 3 * d * f \
        + d * cfg["vocab_size"]


def attn_macs_per_context(cfg) -> int:
    """The score and the weighted value of every query head, every layer."""
    return 2 * cfg["num_layers"] * cfg["num_heads"] * cfg["head_dim"]
