"""Plain reference of the transformer with a lattice memory FFN
(arXiv 2107.03474): forward, MLM loss, and Adam, in straightforward
`jax.numpy`, for the configuration files under `bench/configs/`.

It imports nothing of the program and reads every size from the
configuration file.  The computation dtype is the parameters' dtype:
float32 for the reference (run it under
`jax.default_matmul_precision("highest")`), bfloat16 for the control.
Where the configuration dict has `products` (a dtype name), every operand
of a matrix product is first rounded to it: a control whose products are a
step below the configuration's.

The memory layer, as the paper states it (§2.3-2.6):

* per-head batch norm of the 16 query features, then the torus map: the
  8 angles of 8 complex numbers, scaled to [0, K_i), and the output
  scale 1 / sum_i 1/|z_i|;
* the lattice Lambda = 2*D8 ∪ (2*D8 + 1) (the E8 lattice scaled by 2);
  the rows touched are the lattice points within sqrt(8) of the query,
  weighted by f(r) = max(0, 1 - r^2/8)^4, of which the 32 of largest
  weight are kept.  Here they are found by brute force: every such point
  lies in the shells of squared norm 0, 8 and 16 (2401 vectors) around the
  nearest lattice point, since that point is within the covering radius 2;
* a lattice point's row is its class on the torus prod(K_i Z) in the
  mixed-radix order the configuration states (`torus_K`): with x mod K,
  p = x_1 mod 2, u = (x - p)/2, the row is
  ((u_1..u_7 in radices K_i/2) * (K_8/4) + (u_8 - (sum u_1..u_7 mod 2))/2)
  * 2 + p;
* out = scale * sum_k w_k * values[row_k], per head.

Departures from the published description, each the configuration's own
statement: GELU is the tanh approximation (`act: gelu_tanh`), norms take
eps 1e-6, and attention softmax runs over all positions of a sequence
(no padding mask: no traffic here pads a training sequence).

A configuration file names its reference module (`reference`), and the
benchmark takes from that module, besides the model's mathematics, what
depends on its architecture: the parameter layout that `bench/weights.py`
builds (`layout`) and the model FLOPs that `bench/flops.py` counts
(`macs_per_token`, `attn_macs_per_context`).  This module's are those of a
dense transformer with one memory layer; a module for another architecture
may import this one and replace what differs (`layout(cfg, layer=...)`,
`forward(..., attend=...)`, `train_step_fn(cfg, forward=...)`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

TOP_K_BLOCK = 2048   # query rows per block of the brute-force selection


def _op(cfg, x):
    """An operand of a matrix product, rounded to the dtype the
    configuration's `products` names, if any."""
    prod = cfg.get("products")
    return x if prod is None else x.astype(prod).astype(x.dtype)


# ----------------------------------------------------------------- lattice

@functools.lru_cache(maxsize=None)
def shells() -> np.ndarray:
    """The 2401 points of Lambda with squared norm <= 16."""
    out = []
    for vals in ((-4, -2, 0, 2, 4), (-3, -1, 1, 3)):
        grid = np.stack(np.meshgrid(*[np.array(vals)] * 8, indexing="ij"),
                        -1).reshape(-1, 8)
        keep = ((grid ** 2).sum(1) <= 16) & (grid.sum(1) % 4 == 0)
        out.append(grid[keep])
    pts = np.concatenate(out).astype(np.float32)
    if pts.shape != (2401, 8):
        raise AssertionError(f"expected 2401 shell points, got {pts.shape}")
    return pts


def _nearest_d8(x):
    """Nearest point of D8 (integer vectors with an even sum)."""
    f = jnp.round(x)
    d = x - f
    i = jnp.argmax(jnp.abs(d), axis=-1)
    di = jnp.take_along_axis(d, i[..., None], axis=-1)
    g = f + jax.nn.one_hot(i, 8, dtype=x.dtype) * jnp.where(di >= 0, 1, -1
                                                             ).astype(x.dtype)
    odd = jnp.mod(jnp.sum(f, axis=-1), 2) != 0
    return jnp.where(odd[..., None], g, f)


def nearest_point(q):
    even = 2 * _nearest_d8(q / 2)
    odd = 2 * _nearest_d8((q - 1) / 2) + 1
    de = jnp.sum((q - even) ** 2, axis=-1)
    do = jnp.sum((q - odd) ** 2, axis=-1)
    return jnp.where((de <= do)[..., None], even, odd)


def kernel_weight(d2):
    t = jnp.maximum(0, 1 - d2 / 8)
    return (t * t) * (t * t)


def row_index(points, K):
    """Table row of lattice points (..., 8) on the torus prod(K_i Z)."""
    K = jnp.asarray(K, jnp.int32)
    x = jnp.mod(jnp.round(points).astype(jnp.int32), K)
    p = x[..., 0] % 2
    u = (x - p[..., None]) // 2
    j8 = (u[..., 7] - jnp.sum(u[..., :7], axis=-1) % 2) // 2
    r = jnp.zeros_like(p)
    for i in range(7):
        r = r * (K[i] // 2) + u[..., i]
    return (r * (K[7] // 4) + j8) * 2 + p


def _select(cfg, q, top_k):
    """Offsets (index into `shells`) of the top_k heaviest points near each
    query row of q (n, 8), and the nearest lattice points (n, 8)."""
    s = jnp.asarray(shells(), q.dtype)
    c = nearest_point(q)
    t = q - c
    d2 = (jnp.sum(t * t, -1, keepdims=True)
          - 2 * (_op(cfg, t) @ _op(cfg, s).T)
          + jnp.sum(s * s, -1))
    _, sel = jax.lax.top_k(-d2, top_k)
    return sel, c


def lattice_lookup(cfg, q, K, top_k):
    """(rows, weights) for queries q (..., 8): rows (..., top_k) int32,
    weights differentiable in q."""
    lead = q.shape[:-1]
    flat = jax.lax.stop_gradient(q).reshape(-1, 8)
    n = flat.shape[0]
    blk = min(TOP_K_BLOCK, n)
    pad = (-n) % blk
    blocks = jnp.pad(flat, ((0, pad), (0, 0))).reshape(-1, blk, 8)
    sel, c = jax.lax.map(lambda b: _select(cfg, b, top_k), blocks)
    sel = sel.reshape(-1, top_k)[:n].reshape(*lead, top_k)
    c = c.reshape(-1, 8)[:n].reshape(*lead, 8)
    pts = c[..., None, :] + jnp.asarray(shells(), q.dtype)[sel]
    w = kernel_weight(jnp.sum((q[..., None, :] - pts) ** 2, -1))
    return row_index(pts, K), w


# ------------------------------------------------------------------ layers

def dense(cfg, p, x):
    y = _op(cfg, x) @ _op(cfg, p["kernel"])
    return y + p["bias"] if "bias" in p else y


def norm(cfg, p, x):
    eps = cfg["norm_eps"]
    if cfg["norm"] == "layer":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * p["scale"]


def gelu_tanh(x):
    return 0.5 * x * (1 + jnp.tanh(np.sqrt(2 / np.pi)
                                   * (x + 0.044715 * x ** 3)))


def mlp(cfg, p, x):
    if cfg["act"] == "swiglu":
        g = dense(cfg, p["wi_gate"], x)
        return dense(cfg, p["wo"],
                     g * jax.nn.sigmoid(g) * dense(cfg, p["wi_up"], x))
    return dense(cfg, p["wo"], gelu_tanh(dense(cfg, p["wi"], x)))


def rope(x, theta):
    """Rotary positions on (B, S, H, D): the first half of each head is
    the real part, the second half the imaginary part."""
    s, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.arange(s)[:, None] * freqs[None]
    cos = jnp.asarray(np.cos(ang), x.dtype)[None, :, None]
    sin = jnp.asarray(np.sin(ang), x.dtype)[None, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def softmax_attention(cfg, q, k, v):
    """Softmax attention of q over k and v, each (B, S, H, D), causal for
    the `clm` objective; (B, S, H * D)."""
    b, s, h, hd = q.shape
    scores = jnp.einsum("bshd,bthd->bhst", _op(cfg, q),
                        _op(cfg, k)) / np.sqrt(hd)
    if cfg["objective"] == "clm":
        causal = np.tril(np.ones((s, s), bool))
        scores = jnp.where(causal, scores, -jnp.inf)
    out = jnp.einsum("bhst,bthd->bshd", _op(cfg, jax.nn.softmax(
        scores, -1)), _op(cfg, v))
    return out.reshape(b, s, h * hd)


def attention(cfg, p, x):
    """Multi-head attention: keys and values have `num_heads` heads."""
    b, s, _ = x.shape
    h, hd = cfg["num_heads"], cfg["head_dim"]
    q = dense(cfg, p["wq"], x).reshape(b, s, h, hd)
    k = dense(cfg, p["wk"], x).reshape(b, s, h, hd)
    v = dense(cfg, p["wv"], x).reshape(b, s, h, hd)
    if cfg["pos_scheme"] == "rope":
        q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    return dense(cfg, p["wo"], softmax_attention(cfg, q, k, v))


def memory(cfg, p, st, x, *, train):
    """The memory FFN: dense(w->w), the lattice memory, dense(h*m -> w)."""
    lr = cfg["lram"]
    h = dense(cfg, p["wi"], x)
    lead = h.shape[:-1]
    xh = h.reshape(*lead, lr["heads"], 16)
    qn, run = p["lram"]["qnorm"], st["lram"]["qnorm"]
    if train:
        axes = tuple(range(xh.ndim - 1))
        mean, var = jnp.mean(xh, axes), jnp.var(xh, axes)
        mom = lr["bn_momentum"]
        new = {"mean": mom * run["mean"] + (1 - mom) * mean.astype(
                   run["mean"].dtype),
               "var": mom * run["var"] + (1 - mom) * var.astype(
                   run["var"].dtype)}
    else:
        mean, var = run["mean"].astype(xh.dtype), run["var"].astype(xh.dtype)
        new = run
    xh = (xh - mean) / jnp.sqrt(var + lr["bn_eps"]) * qn["scale"] + qn["bias"]
    re, im = xh[..., :8], xh[..., 8:]
    K = jnp.asarray(lr["torus_K"], xh.dtype)
    q = jnp.mod(jnp.arctan2(im, re) / (2 * np.pi), 1) * K
    scale = 1 / jnp.sum(1 / jnp.sqrt(re * re + im * im), -1, keepdims=True)
    rows, w = lattice_lookup(cfg, q, lr["torus_K"], lr["top_k"])
    values = p["lram"]["values"]
    out = jnp.einsum("...k,...km->...m", _op(cfg, w),
                     _op(cfg, values[rows])) * scale
    y = dense(cfg, p["wo"], out.reshape(*lead, lr["heads"] * lr["m"]))
    return y, {"lram": {"qnorm": new}}


def segments(cfg) -> list[tuple]:
    """[("run", count) | ("memory",)] in layer order: the layers before
    the memory layer, the memory layer, the layers after it."""
    mem, n = cfg["memory_layer"], cfg["num_layers"]
    out = [("run", mem)] if mem else []
    out.append(("memory",))
    if n - mem - 1:
        out.append(("run", n - mem - 1))
    return out


def forward(cfg, params, state, tokens, *, train=False, attend=attention):
    """Logits (B, S, V) and the new batch-norm state; `attend` is every
    layer's attention."""
    x = params["embed"]["embedding"][tokens]
    if cfg["pos_scheme"] == "learned":
        x = x + params["pos_embed"][: tokens.shape[1]]
    new_state = {}
    for si, seg in enumerate(segments(cfg)):
        name = f"seg{si}"
        sp = params["segments"][name]
        if seg[0] == "run":
            for j in range(seg[1]):
                lp = jax.tree.map(lambda a, j=j: a[j], sp)
                x = x + attend(cfg, lp["attn"], norm(cfg, lp["attn_norm"], x))
                x = x + mlp(cfg, lp["mlp"], norm(cfg, lp["ffn_norm"], x))
        else:
            x = x + attend(cfg, sp["attn"], norm(cfg, sp["attn_norm"], x))
            y, new_state[name] = memory(cfg, sp["memffn"], state[name],
                                        norm(cfg, sp["ffn_norm"], x),
                                        train=train)
            x = x + y
    x = norm(cfg, params["final_norm"], x)
    return (_op(cfg, x) @ _op(cfg, params["lm_head"]["kernel"]),
            new_state)


def token_loss(cfg, params, state, batch, *, forward=forward, ignore=-100):
    """Mean cross-entropy of the labels that are not `ignore` (MLM: the
    masked positions; CLM: every next token)."""
    logits, new_state = forward(cfg, params, state, batch["tokens"],
                                train=True)
    labels = batch["labels"]
    valid = labels != ignore
    logp = jax.nn.log_softmax(logits, -1)
    ll = jnp.take_along_axis(logp, jnp.where(valid, labels, 0)[..., None],
                             -1)[..., 0]
    return -jnp.sum(jnp.where(valid, ll, 0)) / jnp.sum(valid), new_state


# ----------------------------------------------------------------- training

def adam_step(opt, params, grads, count, m, v):
    """One Adam step with global-norm clipping and the memory table's
    learning-rate multiplier (`opt` is the configuration's `optimizer`).
    Returns (params, m, v, clipped grads)."""
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                         for g in leaves))
    clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    grads = jax.tree.map(lambda g: (g * clip).astype(g.dtype), grads)
    b1, b2 = opt["b1"], opt["b2"]
    bc1, bc2 = 1 - b1 ** count, 1 - b2 ** count

    def upd(path, p, g, mm, vv):
        name = jax.tree_util.keystr(path)
        lr = opt["lr"] * (opt["memory_lr_mult"]
                          if opt["memory_path"] in name else 1.0)
        mm = b1 * mm + (1 - b1) * g
        vv = b2 * vv + (1 - b2) * g * g
        step = lr * (mm / bc1) / (jnp.sqrt(vv / bc2) + opt["eps"])
        return p - step.astype(p.dtype), mm, vv

    out = jax.tree_util.tree_map_with_path(upd, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2), grads


def train_step_fn(cfg, forward=forward):
    """A jittable (params, state, m, v, count, batch) -> (params, state, m,
    v, loss, clipped grads)."""
    def step(params, state, m, v, count, batch):
        (loss, new_state), grads = jax.value_and_grad(
            lambda p: token_loss(cfg, p, state, batch, forward=forward),
            has_aux=True)(params)
        params, m, v, grads = adam_step(cfg["optimizer"], params, grads,
                                        count, m, v)
        return params, new_state, m, v, loss, grads

    return step


# ------------------------------------------------------- layout and FLOPs

def _norm_shapes(cfg) -> dict:
    d = (cfg["d_model"],)
    return {"scale": d, "bias": d} if cfg["norm"] == "layer" else {"scale": d}


def _dense_shapes(i: int, o: int, bias: bool) -> dict:
    return {"kernel": (i, o), "bias": (o,)} if bias else {"kernel": (i, o)}


def attention_block_shapes(cfg) -> dict:
    """A layer's attention and its two norms: what every layer has."""
    d = cfg["d_model"]
    hd = cfg["num_heads"] * cfg["head_dim"]
    kvd = cfg["num_kv_heads"] * cfg["head_dim"]
    qb = cfg["qkv_bias"]
    attn = {"wq": _dense_shapes(d, hd, qb), "wk": _dense_shapes(d, kvd, qb),
            "wv": _dense_shapes(d, kvd, qb), "wo": _dense_shapes(hd, d, False)}
    return {"attn_norm": _norm_shapes(cfg), "attn": attn,
            "ffn_norm": _norm_shapes(cfg)}


def dense_layer_shapes(cfg) -> dict:
    """A layer with a dense MLP."""
    d, f, mb = cfg["d_model"], cfg["d_ff"], cfg["mlp_bias"]
    if cfg["act"] == "swiglu":
        mlp = {"wi_gate": _dense_shapes(d, f, mb),
               "wi_up": _dense_shapes(d, f, mb), "wo": _dense_shapes(f, d, mb)}
    else:
        mlp = {"wi": _dense_shapes(d, f, mb), "wo": _dense_shapes(f, d, mb)}
    return dict(attention_block_shapes(cfg), mlp=mlp)


def layout(cfg, layer=dense_layer_shapes) -> tuple[dict, dict]:
    """(params, state) as nested dicts of shape tuples, in the program's
    layout: `layer(cfg)` is one layer outside the memory layer, stacked
    over each run of them."""
    d, v = cfg["d_model"], cfg["vocab_size"]
    lr = cfg["lram"]
    params: dict = {"embed": {"embedding": (v, d)},
                    "final_norm": _norm_shapes(cfg)}
    if cfg["pos_scheme"] == "learned":
        params["pos_embed"] = (cfg["max_seq"], d)
    params["lm_head"] = {"kernel": (d, v)}
    segs, state = {}, {}
    for si, seg in enumerate(segments(cfg)):
        if seg[0] == "run":
            segs[f"seg{si}"] = jax.tree.map(
                lambda s, n=seg[1]: (n,) + s, layer(cfg),
                is_leaf=lambda x: isinstance(x, tuple))
        else:
            q = 2 * 8  # real and imaginary parts of 8 complex coordinates
            segs[f"seg{si}"] = dict(attention_block_shapes(cfg), memffn={
                "wi": _dense_shapes(d, d, True),
                "lram": {"values": (2 ** lr["log2_locations"], lr["m"]),
                         "qnorm": {"scale": (q,), "bias": (q,)}},
                "wo": _dense_shapes(lr["heads"] * lr["m"], d, True),
            })
            state[f"seg{si}"] = {"lram": {"qnorm": {"mean": (q,),
                                                   "var": (q,)}}}
    params["segments"] = segs
    return params, state


def attention_projection_macs(cfg) -> int:
    """Multiply-adds per token of one layer's q, k, v and output
    projections."""
    d = cfg["d_model"]
    hd = cfg["num_heads"] * cfg["head_dim"]
    kvd = cfg["num_kv_heads"] * cfg["head_dim"]
    return d * hd + 2 * d * kvd + hd * d


def mlp_macs(cfg) -> int:
    """Multiply-adds per token of one dense MLP."""
    return (3 if cfg["act"] == "swiglu" else 2) * cfg["d_model"] * cfg["d_ff"]


def macs_per_token(cfg) -> int:
    """Multiply-adds per token outside the memory FFN and outside
    attention's context products: every layer's attention projections,
    the MLP of every layer but the memory layer, and the output head."""
    n = cfg["num_layers"]
    return (n * attention_projection_macs(cfg) + (n - 1) * mlp_macs(cfg)
            + cfg["d_model"] * cfg["vocab_size"])


def attn_macs_per_context(cfg) -> int:
    """Multiply-adds per (query, key) pair over all layers: the score and
    the weighted value of every query head."""
    return 2 * cfg["num_layers"] * cfg["num_heads"] * cfg["head_dim"]
