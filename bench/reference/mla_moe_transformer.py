"""Plain reference of a DeepSeek-V3-style causal LM (Moonlight-16B-A3B) with
the lattice memory FFN in one layer: latent attention (MLA) in every
layer, leading dense SwiGLU layers, then expert layers with a sigmoid
router, routed and shared experts, for the configuration files under
`bench/configs/`.  Forward, loss (next-token cross-entropy plus the
sequence-wise balance loss) and Adam, in straightforward `jax.numpy`; it
imports nothing of the program and reads every size from the
configuration file.  Run it under `jax.default_matmul_precision("highest")`.

The equations, as DeepSeek-V2 §2.1 and DeepSeek-V3 §2.1.2 state them:

* MLA, without query compression: q = x W_q, split per head into q_nope
  and q_rope; [c_kv | k_rope] = x W_kv_a; [k_nope | v] = RMSNorm(c_kv)
  W_kv_b per head; RoPE on q_rope and on the one k_rope all heads share;
  score (q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope), causal
  softmax, times v, then W_o.  RoPE takes the first half of a head's rope
  dims as real parts (the checkpoint interleaves them: a permutation of
  the weights).
* Routing: s = sigmoid(x W_r) over all num_experts; the top_k of s + b
  are selected, b a state of zeros that takes no gradient; gates g_i =
  routed_scaling_factor * s_i / sum of the selected s.  The output is the
  shared experts (one SwiGLU of n_shared_experts x the expert width) plus
  sum over the selected experts that are held here of g_i E_i(x).  Every
  held expert runs on every token, masked by its gate: no dispatch.
* The chip's share: experts [0, experts_held) of each expert layer are
  held (the configuration's deployment divides each layer's experts over
  several chips); what the others would add is left out, here as in the
  program.
* Training moves b_i by router_bias_rate * sign(mean load - load_i) over
  the step's tokens and all experts, and adds router_aux_weight times the
  sequence-wise balance loss sum_i f_i P_i (DeepSeek-V3 eqs. 17-20),
  averaged over the batch's sequences and summed over the expert layers.

The memory layer, the norms, the dense SwiGLU and Adam are
`lram_transformer`'s.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import harness

lt = harness.reference("lram_transformer")

MEMORY_BLOCK = 128   # tokens a block of the memory layer's lookup


# ------------------------------------------------------------------ layers

def mla(cfg, p, x):
    b, s, _ = x.shape
    h, r = cfg["num_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    theta = cfg["rope_theta"]
    q = lt.dense(cfg, p["wq"], x).reshape(b, s, h, nope + rope)
    q_nope, q_rope = q[..., :nope], lt.rope(q[..., nope:], theta)
    a = lt.dense(cfg, p["wkv_a"], x)
    c_kv = lt.norm(cfg, p["kv_norm"], a[..., :r])
    k_rope = lt.rope(a[..., None, r:], theta)[:, :, 0]        # (B, S, rope)
    kv = lt.dense(cfg, p["wkv_b"], c_kv).reshape(b, s, h, nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = (jnp.einsum("bshd,bthd->bhst", q_nope, k_nope)
              + jnp.einsum("bshd,btd->bhst", q_rope, k_rope))
    scores = scores / np.sqrt(nope + rope)
    causal = np.tril(np.ones((s, s), bool))
    w = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    out = jnp.einsum("bhst,bthd->bshd", w, v).reshape(b, s, h * dv)
    return lt.dense(cfg, p["wo"], out)


def swiglu(x, gate, up, out):
    g = x @ gate
    return (g * jax.nn.sigmoid(g) * (x @ up)) @ out


def experts(cfg, p, st, x, *, train):
    """The expert layer on x (B, S, d): (y, balance loss, new state)."""
    b, s, d = x.shape
    e, k, held = cfg["num_experts"], cfg["top_k_experts"], cfg["experts_held"]
    xf = x.reshape(b * s, d)
    scores = jax.nn.sigmoid(xf @ p["router"]["kernel"])        # (T, E)
    bias = st["router_bias"]
    _, ids = jax.lax.top_k(scores + bias, k)
    chosen = jax.nn.one_hot(ids, e, dtype=scores.dtype).sum(1)  # (T, E)
    gates = scores * chosen
    if cfg["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    gates = gates * cfg["routed_scaling_factor"]
    ex = p["experts"]
    y = sum(gates[:, i:i + 1] * swiglu(xf, ex["wi_gate"][i], ex["wi_up"][i],
                                       ex["wo"][i]) for i in range(held))
    sh = p["shared"]
    y = y + swiglu(xf, sh["wi_gate"]["kernel"], sh["wi_up"]["kernel"],
                   sh["wo"]["kernel"])
    # sequence-wise balance loss: f_i = E / (k S) * selections of i in the
    # sequence, P_i = mean over the sequence of s_i / sum_j s_j
    f = jax.lax.stop_gradient(chosen).reshape(b, s, e).mean(1) * e / k
    prob = (scores / jnp.sum(scores, -1, keepdims=True)).reshape(b, s, e)
    aux = jnp.mean(jnp.sum(f * prob.mean(1), -1))
    if train:
        load = jnp.sum(chosen, 0)
        bias = bias + cfg["router_bias_rate"] * jnp.sign(jnp.mean(load)
                                                         - load)
    return y.reshape(b, s, d), aux, {"router_bias": bias}


def segments(cfg) -> list[tuple]:
    """[("run", count, "mlp" | "moe") | ("memory",)] in layer order: runs
    of dense or expert layers, split at the memory layer."""
    out: list[tuple] = []
    for i in range(cfg["num_layers"]):
        if i == cfg["memory_layer"]:
            out.append(("memory",))
            continue
        ffn = "mlp" if i < cfg["first_k_dense_replace"] else "moe"
        if out and out[-1][0] == "run" and out[-1][2] == ffn:
            out[-1] = ("run", out[-1][1] + 1, ffn)
        else:
            out.append(("run", 1, ffn))
    return out


def memory(cfg, p, st, x, *, train):
    """`lram_transformer.memory` over blocks of tokens, each recomputed in
    the backward, so that the lookup's rows are held for one block at a
    time.  The batch norm's statistics are the whole batch's, computed
    first, as there, and handed to each block as its running ones."""
    lr = cfg["lram"]
    xh = lt.dense(cfg, p["wi"], x).reshape(-1, lr["heads"], 16)
    mean, var = jnp.mean(xh, (0, 1)), jnp.var(xh, (0, 1))
    run, mom = st["lram"]["qnorm"], lr["bn_momentum"]
    new = run
    if train:
        new = {"mean": mom * run["mean"] + (1 - mom) * mean,
               "var": mom * run["var"] + (1 - mom) * var}
    stats = {"lram": {"qnorm": {"mean": mean, "var": var}}}
    n, d = xh.shape[0], x.shape[-1]
    blk = math.gcd(n, MEMORY_BLOCK)
    f = jax.checkpoint(lambda xb: lt.memory(cfg, p, stats, xb,
                                            train=False)[0])
    y = jax.lax.map(f, x.reshape(n // blk, blk, 1, d))
    return y.reshape(x.shape), {"lram": {"qnorm": new}}


def dense_layer(cfg, lp, x):
    x = x + mla(cfg, lp["attn"], lt.norm(cfg, lp["attn_norm"], x))
    return x + lt.mlp(cfg, lp["mlp"], lt.norm(cfg, lp["ffn_norm"], x))


def expert_layer(cfg, lp, st, x, train):
    x = x + mla(cfg, lp["attn"], lt.norm(cfg, lp["attn_norm"], x))
    y, aux, st = experts(cfg, lp["moe"], st,
                         lt.norm(cfg, lp["ffn_norm"], x), train=train)
    return x + y, aux, st


def memory_layer(cfg, lp, st, x, train):
    x = x + mla(cfg, lp["attn"], lt.norm(cfg, lp["attn_norm"], x))
    y, st = memory(cfg, lp["memffn"], st, lt.norm(cfg, lp["ffn_norm"], x),
                   train=train)
    return x + y, st


def forward(cfg, params, state, tokens, *, train=False):
    """(logits (B, S, V), new state, balance loss summed over layers).
    Each layer is recomputed in the backward, which then holds the
    activations of one layer at a time."""
    remat = lambda f: jax.checkpoint(functools.partial(f, cfg),
                                     static_argnums=(3,) if f is not
                                     dense_layer else ())
    x = params["embed"]["embedding"][tokens]
    new_state, aux = {}, 0.0
    for si, seg in enumerate(segments(cfg)):
        name = f"seg{si}"
        sp = params["segments"][name]
        if seg[0] == "memory":
            x, new_state[name] = remat(memory_layer)(sp, state[name], x,
                                                     train)
            continue
        new = []
        for j in range(seg[1]):
            lp = jax.tree.map(lambda a, j=j: a[j], sp)
            if seg[2] == "mlp":
                x = remat(dense_layer)(lp, x)
                continue
            st = {"router_bias": state[name]["moe"]["router_bias"][j]}
            x, a, st = remat(expert_layer)(lp, st, x, train)
            aux = aux + a
            new.append(st["router_bias"])
        if new:
            new_state[name] = {"moe": {"router_bias": jnp.stack(new)}}
    x = lt.norm(cfg, params["final_norm"], x)
    return x @ params["lm_head"]["kernel"], new_state, aux


def loss(cfg, params, state, batch):
    """Mean next-token cross-entropy plus the weighted balance loss."""
    logits, new_state, aux = forward(cfg, params, state, batch["tokens"],
                                     train=True)
    logp = jax.nn.log_softmax(logits, -1)
    ll = jnp.take_along_axis(logp, batch["labels"][..., None], -1)[..., 0]
    return -jnp.mean(ll) + cfg["router_aux_weight"] * aux, new_state


def train_step_fn(cfg):
    """A jittable (params, state, m, v, count, batch) -> (params, state, m,
    v, loss, the clipped gradient's norm by leaf).

    Adam with global-norm clipping and the memory table's learning-rate
    multiplier, as `lram_transformer.adam_step`, but the gradient is
    computed twice: once for its global norm, then again, leaf by leaf
    into Adam.  One chip cannot hold the whole gradient beside the
    parameters and both moments, given and updated, which a step that
    donates nothing keeps at once (16 bytes a parameter, 9.1 GB at 570M
    parameters); so each leaf's gradient is returned as its norm, a
    scalar, which is all `drivers/train.py` reads of it."""
    opt = cfg["optimizer"]
    b1, b2 = opt["b1"], opt["b2"]

    def grads(params, state, batch):
        return jax.value_and_grad(lambda p: loss(cfg, p, state, batch),
                                  has_aux=True)(params)

    def step(params, state, m, v, count, batch):
        (value, new_state), g = grads(params, state, batch)
        norms = jax.tree.map(
            lambda x: jnp.sqrt(jnp.sum(x.astype(jnp.float32) ** 2)), g)
        gnorm = jnp.sqrt(sum(n * n for n in jax.tree.leaves(norms)))
        clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9))
        # the second pass starts after the first has ended, and is not
        # merged with it
        again, clip = jax.lax.optimization_barrier((params, clip))
        g = grads(again, state, batch)[1]
        bc1, bc2 = 1 - b1 ** count, 1 - b2 ** count

        def upd(path, p, g, mm, vv):
            g = (g * clip).astype(g.dtype)
            lr = opt["lr"] * (opt["memory_lr_mult"] if opt["memory_path"]
                              in jax.tree_util.keystr(path) else 1.0)
            mm = b1 * mm + (1 - b1) * g
            vv = b2 * vv + (1 - b2) * g * g
            step = lr * (mm / bc1) / (jnp.sqrt(vv / bc2) + opt["eps"])
            return p - step.astype(p.dtype), mm, vv

        out = jax.tree_util.tree_map_with_path(upd, params, g, m, v)
        pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                      is_leaf=lambda t: isinstance(t, tuple))
        return (pick(0), new_state, pick(1), pick(2), value,
                jax.tree.map(lambda n: n * clip, norms))

    return step


# ------------------------------------------------------- layout and FLOPs

def _kernel(i: int, o: int) -> dict:
    return {"kernel": (i, o)}


def _swiglu_shapes(d: int, f: int) -> dict:
    return {"wi_gate": _kernel(d, f), "wi_up": _kernel(d, f),
            "wo": _kernel(f, d)}


def attention_block_shapes(cfg) -> dict:
    d, h, r = cfg["d_model"], cfg["num_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    attn = {"wq": _kernel(d, h * (nope + rope)),
            "wkv_a": _kernel(d, r + rope), "kv_norm": {"scale": (r,)},
            "wkv_b": _kernel(r, h * (nope + dv)), "wo": _kernel(h * dv, d)}
    return {"attn_norm": {"scale": (d,)}, "attn": attn,
            "ffn_norm": {"scale": (d,)}}


def layer_shapes(cfg, ffn: str) -> dict:
    d, f = cfg["d_model"], cfg["moe_intermediate_size"]
    if ffn == "mlp":
        return dict(attention_block_shapes(cfg),
                    mlp=_swiglu_shapes(d, cfg["d_ff"]))
    held = cfg["experts_held"]
    return dict(attention_block_shapes(cfg), moe={
        "router": _kernel(d, cfg["num_experts"]),
        "experts": {"wi_gate": (held, d, f), "wi_up": (held, d, f),
                    "wo": (held, f, d)},
        "shared": _swiglu_shapes(d, cfg["n_shared_experts"] * f)})


def layout(cfg) -> tuple[dict, dict]:
    """(params, state) as nested dicts of shape tuples, in the program's
    layout: each run of layers stacked, the router's bias a state leaf."""
    d, v = cfg["d_model"], cfg["vocab_size"]
    params: dict = {"embed": {"embedding": (v, d)},
                    "final_norm": {"scale": (d,)},
                    "lm_head": _kernel(d, v)}
    lr = cfg["lram"]
    q = 2 * 8  # real and imaginary parts of 8 complex coordinates
    segs, state = {}, {}
    for si, seg in enumerate(segments(cfg)):
        name = f"seg{si}"
        if seg[0] == "memory":
            segs[name] = dict(attention_block_shapes(cfg), memffn={
                "wi": {"kernel": (d, d), "bias": (d,)},
                "lram": {"values": (2 ** lr["log2_locations"], lr["m"]),
                         "qnorm": {"scale": (q,), "bias": (q,)}},
                "wo": {"kernel": (lr["heads"] * lr["m"], d), "bias": (d,)},
            })
            state[name] = {"lram": {"qnorm": {"mean": (q,), "var": (q,)}}}
            continue
        n, ffn = seg[1], seg[2]
        segs[name] = jax.tree.map(lambda s: (n,) + s, layer_shapes(cfg, ffn),
                                  is_leaf=lambda x: isinstance(x, tuple))
        if ffn == "moe":
            state[name] = {"moe": {"router_bias": (n, cfg["num_experts"])}}
    params["segments"] = segs
    return params, state


def attention_projection_macs(cfg) -> int:
    d, h, r = cfg["d_model"], cfg["num_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    return (d * h * (nope + rope) + d * (r + rope) + r * h * (nope + dv)
            + h * dv * d)


def macs_per_token(cfg) -> int:
    """Multiply-adds per token outside the memory FFN and attention's
    context products: every layer's MLA projections; the dense layers'
    SwiGLU; in each expert layer the router over all experts, the routed
    experts a token meets here (top_k x experts_held / num_experts of
    them) and the shared experts; the output head."""
    d, n = cfg["d_model"], cfg["num_layers"]
    f = cfg["moe_intermediate_size"]
    dense = cfg["first_k_dense_replace"]
    moe_layers = n - dense - 1
    met = cfg["top_k_experts"] * cfg["experts_held"] / cfg["num_experts"]
    expert = (d * cfg["num_experts"]
              + (met + cfg["n_shared_experts"]) * 3 * d * f)
    return int(n * attention_projection_macs(cfg)
               + dense * 3 * d * cfg["d_ff"] + moe_layers * expert
               + d * cfg["vocab_size"])


def attn_macs_per_context(cfg) -> int:
    """Per (query, key) pair, every layer: each head's score over
    nope + rope dims and its weighted value of v_head_dim."""
    return cfg["num_layers"] * cfg["num_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])
