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


def attention(cfg, p, x):
    b, s, _ = x.shape
    h, hd = cfg["num_heads"], cfg["head_dim"]
    q = dense(cfg, p["wq"], x).reshape(b, s, h, hd)
    k = dense(cfg, p["wk"], x).reshape(b, s, h, hd)
    v = dense(cfg, p["wv"], x).reshape(b, s, h, hd)
    if cfg["pos_scheme"] == "rope":
        q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    scores = jnp.einsum("bshd,bthd->bhst", _op(cfg, q),
                        _op(cfg, k)) / np.sqrt(hd)
    if cfg["objective"] == "clm":
        causal = np.tril(np.ones((s, s), bool))
        scores = jnp.where(causal, scores, -jnp.inf)
    out = jnp.einsum("bhst,bthd->bshd", _op(cfg, jax.nn.softmax(
        scores, -1)), _op(cfg, v))
    return dense(cfg, p["wo"], out.reshape(b, s, h * hd))


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


def forward(cfg, params, state, tokens, *, train=False):
    """Logits (B, S, V) and the new batch-norm state."""
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
                x = x + attention(cfg, lp["attn"], norm(cfg, lp["attn_norm"],
                                                         x))
                x = x + mlp(cfg, lp["mlp"], norm(cfg, lp["ffn_norm"], x))
        else:
            x = x + attention(cfg, sp["attn"], norm(cfg, sp["attn_norm"], x))
            y, new_state[name] = memory(cfg, sp["memffn"], state[name],
                                        norm(cfg, sp["ffn_norm"], x),
                                        train=train)
            x = x + y
    x = norm(cfg, params["final_norm"], x)
    return (_op(cfg, x) @ _op(cfg, params["lm_head"]["kernel"]),
            new_state)


def mlm_loss(cfg, params, state, batch, *, ignore=-100):
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


def train_step_fn(cfg):
    """A jittable (params, state, m, v, count, batch) -> (params, state, m,
    v, loss, clipped grads)."""
    def step(params, state, m, v, count, batch):
        (loss, new_state), grads = jax.value_and_grad(
            lambda p: mlm_loss(cfg, p, state, batch), has_aux=True)(params)
        params, m, v, grads = adam_step(cfg["optimizer"], params, grads,
                                        count, m, v)
        return params, new_state, m, v, loss, grads

    return step
