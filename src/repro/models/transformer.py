"""Generic transformer assembly for every assigned architecture.

The stack is compiled as a sequence of *segments*:

  * ("run", n, ffn)  — n identical layers executed under jax.lax.scan with
                       stacked params (compile time O(1) in depth — essential
                       for 80-layer models x 80 dry-run compiles); ffn is
                       "mlp" or "moe", so a model's leading dense layers
                       (`first_k_dense_replace`) are a run of their own,
  * ("memory", kind) — a single un-scanned layer whose FFN is replaced by the
                       paper's LRAM block (or the PKM baseline). Un-scanned
                       because it carries batchnorm state and its own shapes.
  * hybrid family    — zamba2: units of `hybrid_pattern` mamba blocks + one
                       invocation of a SHARED attention+MLP block, scanned
                       over units with the shared params closed over
                       (parameter sharing across depth, zamba2-style).

Modes: full-sequence (train / prefill, builds KV caches) and single-token
decode (consumes ring/linear caches).  Caches for scanned runs are stacked
along the layer axis and threaded through the scan as xs/ys.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import nn
from repro.core import lram as lram_mod
from repro.core import pkm as pkm_mod
from repro.models import attention, mamba2, mlp, moe
from repro.models.config import ModelConfig

IGNORE = -100


# ---------------------------------------------------------------------------
# Segment plan
# ---------------------------------------------------------------------------

def layer_plan(cfg: ModelConfig) -> list[tuple]:
    """[("run", count, ffn) | ("memory", layer_idx, kind)] covering all
    layers; ffn is "moe" for an expert layer, else "mlp"."""
    special = {i: "lram" for i in cfg.lram_layers}
    special.update({i: "pkm" for i in cfg.pkm_layers})
    if cfg.family == "hybrid":
        assert not special, "memory layers inside hybrid units not supported"
        assert cfg.num_layers % cfg.hybrid_pattern == 0
        return [("hybrid", cfg.num_layers // cfg.hybrid_pattern)]
    plan: list[tuple] = []
    for i in range(cfg.num_layers):
        ffn = "moe" if cfg.moe_layer(i) else "mlp"
        if i in special:
            plan.append(("memory", i, special[i]))
        elif plan and plan[-1][0] == "run" and plan[-1][2] == ffn:
            plan[-1] = ("run", plan[-1][1] + 1, ffn)
        else:
            plan.append(("run", 1, ffn))
    return plan


# ---------------------------------------------------------------------------
# Single-layer blocks
# ---------------------------------------------------------------------------

def _norm_init(cfg, dtype):
    if cfg.norm == "layer":
        return nn.layernorm_init(cfg.d_model, dtype=dtype)
    return nn.rmsnorm_init(cfg.d_model, dtype=dtype)


def _norm(cfg, params, x):
    if cfg.norm == "layer":
        return nn.layernorm(params, x)
    return nn.rmsnorm(params, x, eps=cfg.rms_norm_eps)


def _layer_init(key, cfg: ModelConfig, *, dtype, cross: bool = False,
                ffn: str = "mlp"):
    """(params, state) of one layer; only an expert layer has state."""
    ks = jax.random.split(key, 6)
    if cfg.family == "ssm":
        return {
            "norm": _norm_init(cfg, dtype),
            "mamba": mamba2.mamba_init(ks[0], cfg, dtype=dtype),
        }, {}
    p = {
        "attn_norm": _norm_init(cfg, dtype),
        "attn": attention.attn_init(ks[0], cfg, dtype=dtype),
        "ffn_norm": _norm_init(cfg, dtype),
    }
    if cross:
        p["cross_norm"] = _norm_init(cfg, dtype)
        p["cross"] = attention.attn_init(ks[1], cfg, dtype=dtype)
    if ffn == "moe":
        p["moe"], state = moe.moe_init(ks[2], cfg, dtype=dtype)
        return p, ({"moe": state} if state else {})
    p["mlp"] = mlp.mlp_init(ks[2], cfg, dtype=dtype)
    return p, {}


def _layer_full(lp, x, cfg: ModelConfig, positions, *, causal,
                enc_out=None, st=None, train=False):
    """Full-sequence layer. Returns (x, kv, aux, new_st, counters); an
    expert layer's counters are `moe.moe_apply`'s, a dense layer's None."""
    aux = jnp.zeros((), jnp.float32)
    st = {} if st is None else st
    if cfg.family == "ssm":
        x = x + mamba2.mamba_apply(lp["mamba"], _norm(cfg, lp["norm"], x), cfg)
        return x, None, aux, st, None
    h, kv = attention.attn_apply(
        lp["attn"], _norm(cfg, lp["attn_norm"], x), cfg,
        positions=positions, causal=causal,
    )
    x = x + h
    if "cross" in lp:
        ek, ev = enc_out
        h, _ = attention.attn_apply(
            lp["cross"], _norm(cfg, lp["cross_norm"], x), cfg,
            positions=positions, causal=False, cross_kv=(ek, ev),
        )
        x = x + h
    y = _norm(cfg, lp["ffn_norm"], x)
    counters = None
    if "moe" in lp:
        y, aux, moe_st, counters = moe.moe_apply(
            lp["moe"], st.get("moe", {}), y, cfg, train=train)
        if moe_st:
            st = {"moe": moe_st}
    else:
        y = mlp.mlp_apply(lp["mlp"], y, cfg)
    return x + y, kv, aux, st, counters


def _layer_decode(lp, x, cfg: ModelConfig, pos, cache, *, enc_out=None,
                  st=None):
    """Single-token decode. Returns (x, new_cache)."""
    if cfg.family == "ssm":
        h, new_cache = mamba2.mamba_decode(
            lp["mamba"], _norm(cfg, lp["norm"], x), cfg, cache
        )
        return x + h, new_cache
    h, nk, nv = attention.attn_decode(
        lp["attn"], _norm(cfg, lp["attn_norm"], x), cfg,
        pos=pos, k_cache=cache["k"], v_cache=cache["v"],
    )
    x = x + h
    new_cache = dict(cache, k=nk, v=nv)
    if "cross" in lp:
        h, _, _ = attention.attn_decode(
            lp["cross"], _norm(cfg, lp["cross_norm"], x), cfg,
            pos=pos, k_cache=cache["ck"], v_cache=cache["cv"], cross=True,
        )
        x = x + h
    y = _norm(cfg, lp["ffn_norm"], x)
    if "moe" in lp:
        y = moe.moe_apply(lp["moe"], (st or {}).get("moe", {}), y, cfg)[0]
    else:
        y = mlp.mlp_apply(lp["mlp"], y, cfg)
    return x + y, new_cache


# ---------------------------------------------------------------------------
# Memory (LRAM / PKM) layers: attention + memory-FFN
# ---------------------------------------------------------------------------

def _memory_layer_init(key, cfg: ModelConfig, kind: str, *, dtype):
    ks = jax.random.split(key, 3)
    p = {
        "attn_norm": _norm_init(cfg, dtype),
        "attn": attention.attn_init(ks[0], cfg, dtype=dtype),
        "ffn_norm": _norm_init(cfg, dtype),
    }
    state: dict[str, Any] = {}
    if kind == "lram":
        p["memffn"], state = lram_mod.memffn_init(
            ks[1], cfg.d_model, cfg.lram, dtype=dtype
        )
    else:
        p["pkm"], state = pkm_mod.pkm_init(ks[1], cfg.d_model, cfg.pkm,
                                           dtype=dtype)
    return p, state


def _memory_layer_full(lp, st, x, cfg, positions, kind, *, causal, train,
                       collect_access: bool = False):
    access = None
    if cfg.family != "ssm":
        h, kv = attention.attn_apply(
            lp["attn"], _norm(cfg, lp["attn_norm"], x), cfg,
            positions=positions, causal=causal,
        )
        x = x + h
    else:
        # attention-free host: LRAM block inserted directly on the residual
        # stream (paper §6: sparse memory for recurrent architectures)
        kv = None
    y = _norm(cfg, lp["ffn_norm"], x)
    if kind == "lram":
        if collect_access:
            q = nn.dense(lp["memffn"]["wi"], y)
            hh, new_st, access = lram_mod.lram_apply(
                lp["memffn"]["lram"], st["lram"], q, cfg.lram, train=train,
                return_access=True,
            )
            h = nn.dense(lp["memffn"]["wo"], hh)
            new_st = {"lram": new_st}
        else:
            h, new_st = lram_mod.memffn_apply(
                lp["memffn"], st, y, cfg.lram, train=train
            )
    else:
        if collect_access:
            h, new_st, access = pkm_mod.pkm_apply(
                lp["pkm"], st, y, cfg.pkm, train=train, return_access=True
            )
        else:
            h, new_st = pkm_mod.pkm_apply(lp["pkm"], st, y, cfg.pkm,
                                          train=train)
    return x + h, kv, new_st, access


def _memory_layer_decode(lp, st, x, cfg, pos, cache, kind):
    if cfg.family == "ssm":
        y = _norm(cfg, lp["ffn_norm"], x)
        if kind == "lram":
            h, _ = lram_mod.memffn_apply(lp["memffn"], st, y, cfg.lram)
        else:
            h, _ = pkm_mod.pkm_apply(lp["pkm"], st, y, cfg.pkm)
        return x + h, cache
    h, nk, nv = attention.attn_decode(
        lp["attn"], _norm(cfg, lp["attn_norm"], x), cfg,
        pos=pos, k_cache=cache["k"], v_cache=cache["v"],
    )
    x = x + h
    y = _norm(cfg, lp["ffn_norm"], x)
    if kind == "lram":
        h, _ = lram_mod.memffn_apply(lp["memffn"], st, y, cfg.lram)
    else:
        h, _ = pkm_mod.pkm_apply(lp["pkm"], st, y, cfg.pkm)
    return x + h, dict(cache, k=nk, v=nv)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _stack_init(init_fn, key, n: int):
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def init(key, cfg: ModelConfig):
    """Returns (params, state)."""
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 16)
    params: dict[str, Any] = {
        "embed": nn.embedding_init(keys[0], cfg.vocab_size, cfg.d_model,
                                   dtype=dtype),
        "final_norm": _norm_init(cfg, dtype),
    }
    state: dict[str, Any] = {}
    if cfg.pos_scheme == "learned":
        params["pos_embed"] = nn.truncated_normal_init(0.02)(
            keys[1], (cfg.max_seq, cfg.d_model), dtype
        )
    if not cfg.tie_embeddings:
        params["lm_head"] = nn.dense_init(
            keys[2], cfg.d_model, cfg.vocab_size, use_bias=False, dtype=dtype
        )

    if cfg.family == "encdec":
        params["enc_pos_embed"] = nn.truncated_normal_init(0.02)(
            keys[3], (cfg.encoder_len, cfg.d_model), dtype
        )
        params["encoder"] = _stack_init(
            lambda k: _layer_init(k, cfg, dtype=dtype)[0],
            keys[4], cfg.encoder_layers,
        )
        params["enc_norm"] = _norm_init(cfg, dtype)

    segs: dict[str, Any] = {}
    for si, seg in enumerate(layer_plan(cfg)):
        kseg = jax.random.fold_in(keys[5], si)
        if seg[0] == "run":
            cross = cfg.family == "encdec"
            segs[f"seg{si}"], st = _stack_init(
                lambda k: _layer_init(k, cfg, dtype=dtype, cross=cross,
                                      ffn=seg[2]),
                kseg, seg[1],
            )
            if st:
                state[f"seg{si}"] = st
        elif seg[0] == "hybrid":
            ssm_cfg = dataclasses.replace(cfg, family="ssm")
            unit_init = lambda k: _stack_init(
                lambda kk: _layer_init(kk, ssm_cfg, dtype=dtype)[0],
                k, cfg.hybrid_pattern,
            )
            segs[f"seg{si}"] = _stack_init(unit_init, kseg, seg[1])
            dense_cfg = dataclasses.replace(cfg, family="dense")
            params["shared_attn"] = _layer_init(
                keys[6], dense_cfg, dtype=dtype
            )[0]
        else:
            _, idx, kind = seg
            segs[f"seg{si}"], st = _memory_layer_init(kseg, cfg, kind,
                                                      dtype=dtype)
            state[f"seg{si}"] = st
    params["segments"] = segs
    return params, state


# ---------------------------------------------------------------------------
# Forward (full sequence)
# ---------------------------------------------------------------------------

def _embed_inputs(params, batch, cfg: ModelConfig):
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = jnp.take(params["embed"]["embedding"], tokens, axis=0)
    if cfg.vision_tokens and "vision_embeds" in batch:
        vt = cfg.vision_tokens
        x = jnp.concatenate(
            [batch["vision_embeds"].astype(x.dtype), x[:, vt:]], axis=1
        )
    if cfg.pos_scheme == "learned":
        x = x + params["pos_embed"][:s][None].astype(x.dtype)
    if cfg.pos_scheme == "mrope":
        positions = batch.get(
            "positions",
            jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (3, b, s)),
        )
    else:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    return x, positions


def _maybe_remat(fn, cfg: ModelConfig):
    if not cfg.remat:
        return fn
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    )


def _scan_layers(body, x, stacked, cfg: ModelConfig):
    """lax.scan over stacked layer params, or an unrolled python loop.

    Unrolled mode exists for the dry-run: XLA's cost_analysis counts a
    while-loop body ONCE regardless of trip count, so roofline FLOP/byte
    accounting requires the unrolled graph.  Both modes share params layout.
    """
    if cfg.scan_layers:
        return jax.lax.scan(body, x, stacked)
    n = jax.tree.leaves(stacked)[0].shape[0]
    ys = []
    for i in range(n):
        x, y = body(x, jax.tree.map(lambda a: a[i], stacked))
        ys.append(y)
    if ys and ys[0] is not None:
        stacked_ys = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
    else:
        stacked_ys = None
    return x, stacked_ys


def _run_encoder(params, batch, cfg: ModelConfig):
    x = batch["encoder_embeds"].astype(jnp.dtype(cfg.dtype))
    s = x.shape[1]
    x = x + params["enc_pos_embed"][:s][None].astype(x.dtype)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32),
                                 (x.shape[0], s))
    enc_cfg = dataclasses.replace(cfg, attn_impl="dense")

    def body(x, lp):
        y = _layer_full(lp, x, enc_cfg, positions, causal=False)[0]
        return y, None

    x, _ = _scan_layers(_maybe_remat(body, cfg), x, params["encoder"], cfg)
    return _norm(cfg, params["enc_norm"], x)


def forward(params, state, batch, cfg: ModelConfig, *, train: bool = False,
            collect_access: bool = False):
    """Full-sequence forward. Returns (logits, new_state, aux_loss)
    [+ memory-access dict {seg: (idx, w)} when collect_access=True]."""
    logits, new_state, aux, accesses, _ = _forward(
        params, state, batch, cfg, train=train, collect_access=collect_access)
    if collect_access:
        return logits, new_state, aux, accesses
    return logits, new_state, aux


def _merge_counters(total, layer):
    """Expert-layer counters summed over layers, but the largest expert
    load, which is the most over layers."""
    if layer is None:
        return total
    layer = {"routed_copies": jnp.sum(layer["routed_copies"]),
             "max_expert_load": jnp.max(layer["max_expert_load"])}
    if total is None:
        return layer
    return {"routed_copies": total["routed_copies"] + layer["routed_copies"],
            "max_expert_load": jnp.maximum(total["max_expert_load"],
                                           layer["max_expert_load"])}


def _forward(params, state, batch, cfg: ModelConfig, *, train: bool,
             collect_access: bool):
    """(logits, new_state, aux_loss, accesses, expert counters or None)."""
    causal = cfg.objective == "clm"
    accesses: dict[str, Any] = {}
    counters = None
    x, positions = _embed_inputs(params, batch, cfg)
    enc_kv = None
    if cfg.family == "encdec":
        enc_x = _run_encoder(params, batch, cfg)
        enc_kv = enc_x  # projected per layer below

    new_state: dict[str, Any] = {}
    aux_total = jnp.zeros((), jnp.float32)
    for si, seg in enumerate(layer_plan(cfg)):
        name = f"seg{si}"
        sp = params["segments"][name]
        if seg[0] == "run":
            def body(x, lp_st):
                lp, st = lp_st
                enc = None
                if enc_kv is not None:
                    b, t = enc_kv.shape[:2]
                    ek = nn.dense(lp["cross"]["wk"], enc_kv).reshape(
                        b, t, cfg.num_kv_heads, cfg.head_dim
                    )
                    ev = nn.dense(lp["cross"]["wv"], enc_kv).reshape(
                        b, t, cfg.num_kv_heads, cfg.head_dim
                    )
                    enc = (ek, ev)
                y, _, aux, st, cnt = _layer_full(
                    lp, x, cfg, positions, causal=causal, enc_out=enc,
                    st=st, train=train)
                return y, (aux, st, cnt)

            x, (auxs, st, cnt) = _scan_layers(
                _maybe_remat(body, cfg), x, (sp, state.get(name, {})), cfg)
            aux_total = aux_total + auxs.sum()
            counters = _merge_counters(counters, cnt)
            if st:
                new_state[name] = st
        elif seg[0] == "hybrid":
            shared = params["shared_attn"]
            ssm_cfg = dataclasses.replace(cfg, family="ssm")
            dense_cfg = dataclasses.replace(cfg, family="dense")

            def unit(x, up):
                def mbody(x, lp):
                    y = _layer_full(lp, x, ssm_cfg, positions,
                                    causal=True)[0]
                    return y, None
                x, _ = _scan_layers(mbody, x, up, cfg)
                y = _layer_full(shared, x, dense_cfg, positions,
                                causal=True)[0]
                return y, None

            x, _ = _scan_layers(_maybe_remat(unit, cfg), x, sp, cfg)
        else:
            _, idx, kind = seg
            x, _, st, access = _memory_layer_full(
                sp, state[name], x, cfg, positions, kind,
                causal=causal, train=train, collect_access=collect_access,
            )
            new_state[name] = st
            if access is not None:
                accesses[name] = access

    x = _norm(cfg, params["final_norm"], x)
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["embedding"].astype(x.dtype).T
    else:
        logits = nn.dense(params["lm_head"], x)
    return logits, new_state or state, aux_total, accesses, counters


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def loss_fn(params, state, batch, cfg: ModelConfig, *, train: bool = True,
            collect_access: bool = False):
    """Scalar loss + aux.  `collect_access=True` additionally returns the
    memory-access dict {seg: (idx, w)} from the forward pass (the
    telemetry train step scatter-adds `idx` into its usage counters)."""
    logits, new_state, aux, accesses, counters = _forward(
        params, state, batch, cfg, train=train, collect_access=collect_access
    )
    labels = batch["labels"]
    valid = labels != IGNORE
    safe_labels = jnp.where(valid, labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    tok_ll = jnp.take_along_axis(logp, safe_labels[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(valid.sum(), 1)
    xent = -(tok_ll * valid).sum() / denom
    loss = xent + cfg.router_aux_weight * aux
    metrics = {"xent": xent, "aux": aux, "ntokens": denom}
    if counters is not None:
        # program counters of the expert layers (`moe.moe_apply`)
        metrics.update({f"moe.{k}": v for k, v in counters.items()})
    if collect_access:
        return loss, (new_state, metrics, accesses)
    return loss, (new_state, metrics)


# ---------------------------------------------------------------------------
# KV-cache serving: cache construction, prefill, decode
# ---------------------------------------------------------------------------

def _attn_cache_len(cfg: ModelConfig, max_len: int) -> int:
    if cfg.attention == "swa":
        return min(cfg.window, max_len)
    return max_len


def cache_shapes(cfg: ModelConfig, batch: int, max_len: int):
    """Nested dict of (shape, dtype) — basis for zeros-init AND dry-run
    ShapeDtypeStructs (no allocation)."""
    if cfg.attention == "mla":
        raise NotImplementedError(
            f"{cfg.name}: latent attention has no decode cache")
    dtype = jnp.dtype(cfg.dtype)
    t = _attn_cache_len(cfg, max_len)
    kvd = (batch, t, cfg.num_kv_heads, cfg.head_dim)
    shapes: dict[str, Any] = {}
    for si, seg in enumerate(layer_plan(cfg)):
        name = f"seg{si}"
        if seg[0] == "run":
            n = seg[1]
            if cfg.family == "ssm":
                ms = mamba2.mamba_cache_shapes(cfg, batch)
                shapes[name] = {
                    "ssm": ((n,) + ms["ssm"], jnp.float32),
                    "conv": ((n,) + ms["conv"], jnp.float32),
                }
            else:
                shapes[name] = {
                    "k": ((n,) + kvd, dtype),
                    "v": ((n,) + kvd, dtype),
                }
                if cfg.family == "encdec":
                    ckv = (batch, cfg.encoder_len, cfg.num_kv_heads,
                           cfg.head_dim)
                    shapes[name]["ck"] = ((n,) + ckv, dtype)
                    shapes[name]["cv"] = ((n,) + ckv, dtype)
        elif seg[0] == "hybrid":
            units = seg[1]
            ms = mamba2.mamba_cache_shapes(cfg, batch)
            shapes[name] = {
                "ssm": ((units, cfg.hybrid_pattern) + ms["ssm"], jnp.float32),
                "conv": ((units, cfg.hybrid_pattern) + ms["conv"],
                         jnp.float32),
                "k": ((units,) + kvd, dtype),
                "v": ((units,) + kvd, dtype),
            }
        else:
            if cfg.family == "ssm":
                shapes[name] = {}
            else:
                shapes[name] = {"k": (kvd, dtype), "v": (kvd, dtype)}
    return shapes


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    return jax.tree.map(
        lambda sd: jnp.zeros(sd[0], sd[1]),
        cache_shapes(cfg, batch, max_len),
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple),
    )


def cache_specs(cfg: ModelConfig, batch: int, max_len: int):
    return jax.tree.map(
        lambda sd: jax.ShapeDtypeStruct(sd[0], sd[1]),
        cache_shapes(cfg, batch, max_len),
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple),
    )


def decode_step(params, state, tokens, pos, cache, cfg: ModelConfig,
                batch_extras: Optional[dict] = None):
    """One serving step: tokens (B, 1) at absolute position `pos`.

    `pos` is a scalar (the classic lockstep batch) or an int32 vector (B,)
    carrying one absolute position per batch slot — the continuous-batching
    engine (repro.serving) drives every decode through the vector form, so
    sequences at different depths share one fixed-shape compiled step.

    Returns (logits (B, 1, V), new_cache).  This is the function the
    `decode_*` / `long_*` dry-run cells lower.
    """
    b = tokens.shape[0]
    x = jnp.take(params["embed"]["embedding"], tokens, axis=0)
    if cfg.pos_scheme == "learned":
        if jnp.ndim(pos) == 1:
            x = x + jnp.take(
                params["pos_embed"],
                jnp.minimum(pos, params["pos_embed"].shape[0] - 1), axis=0,
            )[:, None].astype(x.dtype)
        else:
            x = x + jax.lax.dynamic_slice_in_dim(
                params["pos_embed"], pos, 1, axis=0
            )[None].astype(x.dtype)

    new_cache: dict[str, Any] = {}
    for si, seg in enumerate(layer_plan(cfg)):
        name = f"seg{si}"
        sp = params["segments"][name]
        c = cache[name]
        if seg[0] == "run":
            def body(x, lp_c):
                lp, st, ci = lp_c
                y, co = _layer_decode(lp, x, cfg, pos, ci, st=st)
                return y, co

            x, co = _scan_layers(body, x, (sp, state.get(name, {}), c), cfg)
            new_cache[name] = co
        elif seg[0] == "hybrid":
            shared = params["shared_attn"]
            ssm_cfg = dataclasses.replace(cfg, family="ssm")
            dense_cfg = dataclasses.replace(cfg, family="dense")

            def unit(x, up_c):
                up, ci = up_c

                def mbody(x, lp_mc):
                    lp, mc = lp_mc
                    y, co = _layer_decode(lp, x, ssm_cfg, pos, mc)
                    return y, co

                x, mco = _scan_layers(
                    mbody, x, (up, {"ssm": ci["ssm"], "conv": ci["conv"]}),
                    cfg,
                )
                y, aco = _layer_decode(
                    shared, x, dense_cfg, pos, {"k": ci["k"], "v": ci["v"]}
                )
                return y, {**mco, **aco}

            x, co = _scan_layers(unit, x, (sp, c), cfg)
            new_cache[name] = co
        else:
            _, idx, kind = seg
            x, co = _memory_layer_decode(sp, state[name], x, cfg, pos, c,
                                         kind)
            new_cache[name] = co

    x = _norm(cfg, params["final_norm"], x)
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["embedding"].astype(x.dtype).T
    else:
        logits = nn.dense(params["lm_head"], x)
    return logits, new_cache


# ---------------------------------------------------------------------------
# Slotted KV-cache manager (continuous batching)
# ---------------------------------------------------------------------------
#
# The decode cache built by `init_cache(cfg, B, max_len)` is *slotted*: the
# batch axis is a pool of B fixed-shape slots, each holding one in-flight
# sequence.  The continuous-batching engine (repro.serving) admits a new
# request by prefilling it at batch=1 and splicing the resulting sub-cache
# into a free slot, and retires a finished one by simply marking the slot
# free — the next admission overwrites every cache position, so no explicit
# clearing is needed.  Because segment kinds stack their caches differently
# (scanned runs carry a leading layer axis, hybrid units two), the batch
# axis is *derived* per leaf rather than assumed.

def cache_batch_axes(cfg: ModelConfig, max_len: int):
    """Pytree matching the cache with each leaf's batch-axis index.

    Derived by diffing `cache_shapes` at two batch sizes — robust to any
    segment layout (plain runs, hybrid units, memory layers) without
    hard-coding per-family axis positions."""
    one = cache_shapes(cfg, 1, max_len)
    two = cache_shapes(cfg, 2, max_len)
    is_leaf = lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)

    def axis(a, b):
        for i, (da, db) in enumerate(zip(a[0], b[0])):
            if da != db:
                return i
        raise ValueError(f"cache leaf {a[0]} has no batch axis")

    return jax.tree.map(axis, one, two, is_leaf=is_leaf)


def write_cache_slot(cache, sub_cache, slot, axes):
    """Splice a batch=1 `sub_cache` (from a single-request prefill) into
    batch slot `slot` of a slotted cache.  `axes` comes from
    `cache_batch_axes`; `slot` may be traced (the engine jits this with the
    big cache donated, so admission never copies the pool)."""
    def upd(c, s, ax):
        return jax.lax.dynamic_update_slice_in_dim(
            c, s.astype(c.dtype), slot, axis=ax
        )

    return jax.tree.map(upd, cache, sub_cache, axes)


def _fill_kv_cache(k_new, v_new, cfg: ModelConfig, t_cache: int, s: int):
    """Map prefill K/V (.., s, Kh, D) onto the decode cache layout.

    Full attention: slot = position (pad tail).  SWA ring buffer:
    slot = position % window — the last `window` positions hit each slot
    exactly once, so the fill is the argsort permutation of their slots.
    Works for arrays with any number of leading dims before the seq axis -2
    ... here seq axis is -3 (…, s, Kh, D)."""
    if cfg.attention == "swa" and s > t_cache:
        keep = np.arange(s - t_cache, s)
        order = np.argsort(keep % t_cache)
        k_new = jnp.take(k_new, jnp.asarray(keep[order]), axis=-3)
        v_new = jnp.take(v_new, jnp.asarray(keep[order]), axis=-3)
    pad = t_cache - k_new.shape[-3]
    if pad > 0:
        widths = [(0, 0)] * k_new.ndim
        widths[-3] = (0, pad)
        k_new = jnp.pad(k_new, widths)
        v_new = jnp.pad(v_new, widths)
    return k_new, v_new


def _mamba_prefill_body(lp, x, cfg: ModelConfig, s: int):
    """Mamba layer full forward that also emits (final_state, conv_tail)."""
    u = _norm(cfg, lp["norm"], x)
    z, xbc_raw, dt_raw = mamba2._split_proj(lp["mamba"], u, cfg)
    xbc = mamba2._causal_conv(xbc_raw, lp["mamba"]["conv"])
    xx, B, C, dt = mamba2._post_conv(xbc, dt_raw, lp["mamba"], cfg)
    A = -jnp.exp(lp["mamba"]["A_log"])
    if s % cfg.ssm_chunk == 0 and s > 1:
        y, hf = mamba2.ssd_chunked(xx, B, C, dt, A, chunk=cfg.ssm_chunk)
    else:
        y, hf = mamba2.ssd_sequential(xx, B, C, dt, A)
    y = y + lp["mamba"]["D"][:, None] * xx.astype(jnp.float32)
    y = y.reshape(*u.shape[:-1], cfg.d_inner)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    y = nn.rmsnorm(lp["mamba"]["norm"], y)
    y = nn.dense(lp["mamba"]["out_proj"], y.astype(u.dtype))
    nconv = cfg.ssm_conv - 1
    if s >= nconv:
        conv_tail = xbc_raw[:, s - nconv:, :]
    else:
        conv_tail = jnp.pad(xbc_raw, ((0, 0), (nconv - s, 0), (0, 0)))
    return x + y, hf, conv_tail.astype(jnp.float32)


def prefill(params, state, batch, cfg: ModelConfig, max_len: int):
    """Run the full prompt, building decode caches. Returns (logits, cache).

    Supports every family; the `prefill_*` dry-run cells lower this."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    cache = init_cache(cfg, b, max_len)
    x, positions = _embed_inputs(params, batch, cfg)
    enc_kv = None
    if cfg.family == "encdec":
        enc_kv = _run_encoder(params, batch, cfg)

    def _enc_proj(lp):
        if enc_kv is None:
            return None
        bb, t = enc_kv.shape[:2]
        ek = nn.dense(lp["cross"]["wk"], enc_kv).reshape(
            bb, t, cfg.num_kv_heads, cfg.head_dim)
        ev = nn.dense(lp["cross"]["wv"], enc_kv).reshape(
            bb, t, cfg.num_kv_heads, cfg.head_dim)
        return ek, ev

    for si, seg in enumerate(layer_plan(cfg)):
        name = f"seg{si}"
        sp = params["segments"][name]
        t_attn = _attn_cache_len(cfg, max_len)
        if seg[0] == "run":
            if cfg.family == "ssm":
                def body(x, lp):
                    y, hf, convt = _mamba_prefill_body(lp, x, cfg, s)
                    return y, (hf, convt)

                x, (hf, convt) = _scan_layers(body, x, sp, cfg)
                cache[name] = {"ssm": hf, "conv": convt}
            else:
                def body(x, lp_st):
                    lp, st = lp_st
                    enc = _enc_proj(lp)
                    y, kv, _, _, _ = _layer_full(lp, x, cfg, positions,
                                                 causal=True, enc_out=enc,
                                                 st=st)
                    out = (kv[0], kv[1]) + ((enc[0], enc[1]) if enc else ())
                    return y, out

                x, kvs = _scan_layers(body, x, (sp, state.get(name, {})),
                                      cfg)
                k_new, v_new = _fill_kv_cache(kvs[0], kvs[1], cfg, t_attn, s)
                cache[name]["k"] = k_new
                cache[name]["v"] = v_new
                if cfg.family == "encdec":
                    cache[name]["ck"] = kvs[2]
                    cache[name]["cv"] = kvs[3]
        elif seg[0] == "hybrid":
            shared = params["shared_attn"]
            ssm_cfg = dataclasses.replace(cfg, family="ssm")
            dense_cfg = dataclasses.replace(cfg, family="dense")

            def unit(x, up):
                def mbody(x, lp):
                    y, hf, convt = _mamba_prefill_body(lp, x, ssm_cfg, s)
                    return y, (hf, convt)

                x, (hf, convt) = _scan_layers(mbody, x, up, cfg)
                y, kv = _layer_full(shared, x, dense_cfg, positions,
                                    causal=True)[:2]
                return y, (hf, convt, kv[0], kv[1])

            x, (hf, convt, k_new, v_new) = _scan_layers(unit, x, sp, cfg)
            k_new, v_new = _fill_kv_cache(k_new, v_new, cfg, t_attn, s)
            cache[name] = {"ssm": hf, "conv": convt, "k": k_new, "v": v_new}
        else:
            _, idx, kind = seg
            x, kv, _, _ = _memory_layer_full(
                sp, state[name], x, cfg, positions, kind,
                causal=True, train=False,
            )
            if kv is not None:
                k_new, v_new = _fill_kv_cache(kv[0], kv[1], cfg, t_attn, s)
                cache[name] = {"k": k_new, "v": v_new}

    x = _norm(cfg, params["final_norm"], x)
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["embedding"].astype(x.dtype).T
    else:
        logits = nn.dense(params["lm_head"], x)
    return logits, cache
