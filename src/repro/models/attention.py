"""Attention: GQA, latent attention (MLA), RoPE / M-RoPE, sliding window,
flash-style chunking.

Three execution paths, all numerically identical (tested against each other):

  * dense      — materialises (S, T) scores; short sequences.
  * chunked    — two-level blocking with streaming softmax (running max /
                 denominator carried across KV chunks, scanned over Q chunks).
                 This is the memory-roofline path for 32k prefill: peak
                 activation is O(chunk^2) instead of O(S^2).
  * decode     — single-query step against a (possibly ring-buffered) cache.

All softmax math in float32 regardless of activation dtype.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import nn
from repro.distributed import context
from repro.models.config import ModelConfig

_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (
        theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim)
    )


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) int32."""
    d = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(d, theta))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B,S,D/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def apply_mrope(
    x: jax.Array,
    positions: jax.Array,
    theta: float,
    sections: tuple[int, ...],
) -> jax.Array:
    """Multimodal RoPE (qwen2-vl): positions (3, B, S) for (t, h, w); the
    frequency bands are partitioned across the three position streams."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.asarray(rope_frequencies(d, theta))  # (half,)
    assert sum(sections) == half, (sections, half)
    # build per-band position selection
    band = np.zeros((half,), dtype=np.int32)
    start = 0
    for i, s in enumerate(sections):
        band[start : start + s] = i
        start += s
    band = jnp.asarray(band)
    # angles: select positions[band[j]] for frequency j
    pos = positions.astype(jnp.float32)  # (3, B, S)
    onehot = jax.nn.one_hot(band, 3, dtype=jnp.float32)  # (half, 3)
    sel = jnp.einsum("hc,cbs->bsh", onehot, pos)  # (B, S, half)
    angles = sel * freqs  # (B, S, half)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Core attention math (GQA-aware)
# ---------------------------------------------------------------------------

def _scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: (B, S, Kh, G, D), k: (B, T, Kh, D) -> (B, Kh, G, S, T) in f32."""
    return jnp.einsum(
        "bskgd,btkd->bkgst",
        q.astype(jnp.float32),
        k.astype(jnp.float32),
    )


def _attend(w: jax.Array, v: jax.Array) -> jax.Array:
    """w: (B, Kh, G, S, T), v: (B, T, Kh, D) -> (B, S, Kh, G, D)."""
    return jnp.einsum("bkgst,btkd->bskgd", w, v.astype(w.dtype))


def _band_mask(
    s: int, t: int, *, causal: bool, window: Optional[int], q_offset: int = 0
) -> np.ndarray:
    """(S, T) boolean validity mask. Query i sits at absolute t-position
    q_offset + i."""
    qi = np.arange(s)[:, None] + q_offset
    kj = np.arange(t)[None, :]
    ok = np.ones((s, t), dtype=bool)
    if causal:
        ok &= kj <= qi
    if window is not None:
        ok &= kj > qi - window
    return ok


def dense_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    window: Optional[int] = None,
    q_offset: int = 0,
) -> jax.Array:
    """q: (B,S,H,D), k: (B,T,Kh,D), v: (B,T,Kh,Dv) -> (B,S,H,Dv)."""
    b, s, h, d = q.shape
    kh = k.shape[2]
    qg = q.reshape(b, s, kh, h // kh, d) * (d**-0.5)
    scores = _scores(qg, k)  # (B,Kh,G,S,T)
    mask = _band_mask(s, k.shape[1], causal=causal, window=window,
                      q_offset=q_offset)
    scores = jnp.where(jnp.asarray(mask), scores, _NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = _attend(w, v)
    return out.reshape(b, s, h, v.shape[-1]).astype(q.dtype)


def chunked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    window: Optional[int] = None,
    q_chunk: int = 2048,
    kv_chunk: int = 2048,
) -> jax.Array:
    """Flash-style streaming-softmax attention; O(chunk^2) peak memory.

    Scan over query chunks; inside, scan over KV chunks carrying
    (running_max, denominator, weighted accumulator).  The value width Dv
    may differ from the query/key width D.
    """
    b, s, h, d = q.shape
    t = k.shape[1]
    kh = k.shape[2]
    dv = v.shape[-1]
    assert s % q_chunk == 0 and t % kv_chunk == 0, (s, t, q_chunk, kv_chunk)
    nq, nk = s // q_chunk, t // kv_chunk
    qg = (q.reshape(b, s, kh, h // kh, d) * (d**-0.5))
    qg = qg.reshape(b, nq, q_chunk, kh, h // kh, d)
    kc = k.reshape(b, nk, kv_chunk, kh, d)
    vc = v.reshape(b, nk, kv_chunk, kh, dv)

    def q_step(_, qi_blk):
        qi, q_blk = qi_blk  # q_blk: (B, q_chunk, Kh, G, D)

        def kv_step(carry, kj_blk):
            m, l, acc = carry
            kj, k_blk, v_blk = kj_blk
            scores = jnp.einsum(
                "bskgd,btkd->bkgst",
                q_blk.astype(jnp.float32),
                k_blk.astype(jnp.float32),
            )  # (B,Kh,G,qc,kc)
            # block-relative band mask
            q_pos = qi * q_chunk + jax.lax.broadcasted_iota(
                jnp.int32, scores.shape, 3
            )
            k_pos = kj * kv_chunk + jax.lax.broadcasted_iota(
                jnp.int32, scores.shape, 4
            )
            ok = jnp.ones(scores.shape, bool)
            if causal:
                ok &= k_pos <= q_pos
            if window is not None:
                ok &= k_pos > q_pos - window
            scores = jnp.where(ok, scores, _NEG_INF)
            m_new = jnp.maximum(m, scores.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(scores - m_new[..., None])
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgst,btkd->bkgsd", p, v_blk.astype(jnp.float32)
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kh, h // kh, q_chunk), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kh, h // kh, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, kh, h // kh, q_chunk, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step,
            (m0, l0, a0),
            (jnp.arange(nk), jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0)),
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out  # (B,Kh,G,qc,D)

    _, outs = jax.lax.scan(
        q_step, None, (jnp.arange(nq), jnp.moveaxis(qg, 1, 0))
    )
    # outs: (nq, B, Kh, G, qc, D) -> (B, S, H, D)
    out = jnp.moveaxis(outs, 0, 1)  # (B, nq, Kh, G, qc, D)
    out = jnp.transpose(out, (0, 1, 4, 2, 3, 5)).reshape(b, s, h, dv)
    return out.astype(q.dtype)


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cache_len: jax.Array,
    *,
    ring: bool = False,
) -> jax.Array:
    """Single-token decode. q: (B,1,H,D); caches (B,T,Kh,D).

    `cache_len` — number of valid entries (B,) or scalar. With `ring=True`
    the cache is a circular buffer (SWA): all T slots are valid once full,
    and positions are unordered (softmax is permutation-invariant).
    """
    b, _, h, d = q.shape
    t, kh = k_cache.shape[1], k_cache.shape[2]
    qg = q.reshape(b, 1, kh, h // kh, d) * (d**-0.5)
    scores = _scores(qg, k_cache)  # (B,Kh,G,1,T)
    pos = jnp.arange(t)
    valid = pos[None, :] < jnp.reshape(cache_len, (-1, 1))
    scores = jnp.where(valid[:, None, None, None, :], scores, _NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    # decode reads the whole cache once: keep the attend in the cache dtype
    # (softmax weights <= 1; f32 here would stream 2x the bytes) and pin the
    # weights replicated so the einsum reuses the cache's resident layout.
    B = context.batch_axes()
    mesh = context.get_mesh()
    kh_div = mesh is None or kh % mesh.shape["model"] == 0
    w = context.constrain(w.astype(v_cache.dtype), B, None, None, None, None)
    out = _attend(w, v_cache)  # 'bskgd'
    if kh_div:
        out = context.constrain(out, B, None, "model", None, None)
    else:
        out = context.constrain(out, B, None, None, None, "model")
    return out.reshape(b, 1, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Full attention layer (projections + rope + cache plumbing)
# ---------------------------------------------------------------------------

def attn_init(key, cfg: ModelConfig, *, dtype=jnp.float32):
    if cfg.attention == "mla":
        return mla_init(key, cfg, dtype=dtype)
    kq, kk, kv, ko = jax.random.split(key, 4)
    h, khd, d = cfg.num_heads, cfg.num_kv_heads, cfg.d_model
    hd = cfg.head_dim
    return {
        "wq": nn.dense_init(kq, d, h * hd, use_bias=cfg.qkv_bias, dtype=dtype),
        "wk": nn.dense_init(kk, d, khd * hd, use_bias=cfg.qkv_bias, dtype=dtype),
        "wv": nn.dense_init(kv, d, khd * hd, use_bias=cfg.qkv_bias, dtype=dtype),
        "wo": nn.dense_init(ko, h * hd, d, use_bias=False, dtype=dtype),
    }


def _project_qkv(params, x, cfg: ModelConfig, positions):
    b, s, _ = x.shape
    q = nn.dense(params["wq"], x).reshape(b, s, cfg.num_heads, cfg.head_dim)
    k = nn.dense(params["wk"], x).reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    v = nn.dense(params["wv"], x).reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    if cfg.pos_scheme == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    elif cfg.pos_scheme == "mrope":
        q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    return q, k, v


def attn_apply(
    params,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    causal: bool = True,
    cross_kv: Optional[tuple[jax.Array, jax.Array]] = None,
):
    """Full-sequence attention (train / prefill compute). x: (B, S, d)."""
    if cfg.attention == "mla":
        assert cross_kv is None, "latent attention has no cross-attention"
        return mla_apply(params, x, cfg, positions=positions, causal=causal)
    b, s, _ = x.shape
    q, k, v = _project_qkv(params, x, cfg, positions)
    if cross_kv is not None:
        k, v = cross_kv
    window = cfg.window if cfg.attention == "swa" else None
    out = _attention(q, k, v, cfg, causal=causal, window=window,
                     chunkable=cross_kv is None)
    out = out.reshape(b, s, cfg.num_heads * cfg.head_dim)
    y = nn.dense(params["wo"], out)
    return y, (k, v)


def _attention(q, k, v, cfg: ModelConfig, *, causal, window=None,
               chunkable=True):
    """The dense or the chunked path, as `attn_impl` and the length ask."""
    s = q.shape[1]
    use_chunked = cfg.attn_impl == "chunked" or (
        cfg.attn_impl == "auto" and s > cfg.attn_chunk and chunkable
    )
    if use_chunked and s % cfg.attn_chunk == 0:
        return chunked_attention(
            q, k, v, causal=causal, window=window,
            q_chunk=cfg.attn_chunk, kv_chunk=cfg.attn_chunk,
        )
    return dense_attention(q, k, v, causal=causal, window=window)


# ---------------------------------------------------------------------------
# Latent attention (MLA, DeepSeek-V2 §2.1), without query LoRA
# ---------------------------------------------------------------------------

def mla_init(key, cfg: ModelConfig, *, dtype=jnp.float32):
    kq, ka, kb, ko = jax.random.split(key, 4)
    d, h, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "wq": nn.dense_init(kq, d, h * (nope + rope), use_bias=False,
                            dtype=dtype),
        "wkv_a": nn.dense_init(ka, d, r + rope, use_bias=False, dtype=dtype),
        "kv_norm": nn.rmsnorm_init(r, dtype=dtype),
        "wkv_b": nn.dense_init(kb, r, h * (nope + dv), use_bias=False,
                               dtype=dtype),
        "wo": nn.dense_init(ko, h * dv, d, use_bias=False, dtype=dtype),
    }


def mla_apply(params, x: jax.Array, cfg: ModelConfig, *, positions,
              causal: bool = True):
    """Full-sequence latent attention. x: (B, S, d) -> (y, (k, v)).

    q = x W_q, split into q_nope and q_rope; [c_kv | k_rope] = x W_kv_a;
    [k_nope | v] = RMSNorm(c_kv) W_kv_b; RoPE on q_rope and on the single
    k_rope, which every head shares; scores (q_nope.k_nope + q_rope.k_rope)
    / sqrt(nope + rope).  Keys are built per head, so the attention itself
    is the plain multi-head path with a value width of its own."""
    b, s, _ = x.shape
    h, r = cfg.num_heads, cfg.kv_lora_rank
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    with jax.named_scope("mla"):
        with jax.named_scope("q"):
            q = nn.dense(params["wq"], x).reshape(b, s, h, nope + rope)
            q_rope = apply_rope(q[..., nope:], positions, cfg.rope_theta)
            q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
        with jax.named_scope("kv_latent"):
            a = nn.dense(params["wkv_a"], x)
            c_kv = nn.rmsnorm(params["kv_norm"], a[..., :r],
                              eps=cfg.rms_norm_eps).astype(x.dtype)
            k_rope = apply_rope(a[..., None, r:], positions, cfg.rope_theta)
            kv = nn.dense(params["wkv_b"], c_kv).reshape(b, s, h, nope + dv)
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(k_rope, (b, s, h, rope))], axis=-1)
            v = kv[..., nope:]
        with jax.named_scope("scores"):
            out = _attention(q, k, v, cfg, causal=causal)
        y = nn.dense(params["wo"], out.reshape(b, s, h * dv))
    return y, (k, v)


def attn_decode(
    params,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    pos: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cross: bool = False,
):
    """One decode step. x: (B, 1, d); caches (B, T, Kh, D).

    `pos` is the absolute token position: a scalar (whole batch in lockstep,
    the classic fixed-batch serve loop) or an int32 vector (B,) with one
    position per batch slot (continuous batching: every slot sits at its own
    depth in its own sequence).

    Returns (y, new_k_cache, new_v_cache). For SWA the cache is a ring
    buffer of size `cfg.window`."""
    if cfg.attention == "mla":
        raise NotImplementedError("latent attention has no decode cache")
    b = x.shape[0]
    t = k_cache.shape[1]
    per_slot = jnp.ndim(pos) == 1
    if cfg.pos_scheme == "mrope":
        positions = (jnp.broadcast_to(pos[None, :, None], (3, b, 1))
                     if per_slot else jnp.full((3, b, 1), pos, jnp.int32))
    else:
        positions = (pos[:, None].astype(jnp.int32) if per_slot
                     else jnp.full((b, 1), pos, jnp.int32))
    q, k, v = _project_qkv(params, x, cfg, positions)
    # Pin the decode layout: (batch=data, ..., head_dim=model when kv_heads
    # can't split the axis).  Without this the partitioner "involuntarily
    # fully rematerializes" (all-gathers) the 32k cache on every step —
    # EXPERIMENTS.md §Perf cell 2.
    B = context.batch_axes()
    kh_div = (
        context.get_mesh() is None
        or cfg.num_kv_heads % context.get_mesh().shape["model"] == 0
    )
    kv_spec = (B, None, "model", None) if kh_div else (B, None, None, "model")
    q = context.constrain(q, B, None, None, "model" if not kh_div else None)
    k = context.constrain(k, *kv_spec)
    v = context.constrain(v, *kv_spec)
    if cross:
        # cross-attention: cache is the (static) encoder projection
        out = decode_attention(q, k_cache, v_cache,
                               jnp.full((b,), t, jnp.int32))
        new_k, new_v = k_cache, v_cache
    else:
        ring = cfg.attention == "swa"
        slot = pos % t if ring else pos
        if per_slot:
            # one scatter row per batch element: slot i writes at its own
            # position (the write lands before the attend, so a stale row at
            # the new position can never be read back)
            slot = slot if ring else jnp.minimum(slot, t - 1)
            new_k = k_cache.at[jnp.arange(b), slot].set(k[:, 0])
            new_v = v_cache.at[jnp.arange(b), slot].set(v[:, 0])
        else:
            new_k = jax.lax.dynamic_update_slice_in_dim(
                k_cache, k, slot, axis=1
            )
            new_v = jax.lax.dynamic_update_slice_in_dim(
                v_cache, v, slot, axis=1
            )
        new_k = context.constrain(new_k, *kv_spec)
        new_v = context.constrain(new_v, *kv_spec)
        n_valid = jnp.minimum(pos + 1, t)
        out = decode_attention(
            q, new_k, new_v,
            n_valid.astype(jnp.int32) if per_slot
            else jnp.full((b,), n_valid, jnp.int32),
            ring=ring,
        )
    out = out.reshape(b, 1, -1)
    out = context.constrain(out, B, None, "model")
    y = nn.dense(params["wo"], out)
    return y, new_k, new_v
