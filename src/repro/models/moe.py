"""Dropless top-k Mixture-of-Experts (Mixtral, Phi-3.5-MoE, DeepSeek-V3).

One layer for every MoE architecture, built for expert parallelism: the
router scores every token against all `num_experts` experts, and the
layer holds experts [first, first + experts_held) of them (all of them
unless the configuration says otherwise).  It adds only its own experts'
part of the result, plus the shared experts, which every share adds
whole; what experts held elsewhere would add is theirs to add.  No token is
dropped:

  1. router: softmax (Mixtral) or sigmoid scores (DeepSeek-V3, selection
     by the scores plus a bias that carries no gradient); the top-k gates,
     renormalised over the k and scaled by `routed_scaling_factor`;
  2. dispatch: the T*k token copies sorted by expert;
  3. experts: three grouped matrix products over the sorted copies
     (`megablox` gmm, Pallas), which visit only the row tiles of the held
     experts' groups, so the work scales with the copies routed here;
  4. combine: each copy's output back to its token, weighted by its gate;
  5. shared: a dense SwiGLU of n_shared_experts x the expert width.

Under an ambient mesh with a `model` axis (`distributed.context`), steps
2-4 run in a `shard_map` over it, since the Pallas products are not
partitioned by GSPMD: each device takes its data shard's tokens and
either experts_held / model of the held experts (expert parallelism,
`first` moved by the device's index) or, where the experts do not divide
the axis, a model-th of every expert's hidden width; a psum over `model`
adds the parts.  The weights' layouts are `distributed.sharding`'s.

Training also returns the router's balance loss (Switch's for softmax,
DeepSeek-V3's sequence-wise one for sigmoid) and, for sigmoid routing, the
bias state moved by `BIAS_STEP * sign(mean load - load)`.  SMoE is
the paper's O(sqrt N) comparison point (§5): LRAM replaces this block when
`lram_layers` covers an MoE layer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
from jax.sharding import PartitionSpec as P

from repro import nn
from repro.distributed import context
from repro.kernels import interpret_mode
from repro.models import mlp
from repro.models.config import ModelConfig

_TILE = 128   # gmm tile edge (rows, contraction, output columns)
BIAS_STEP = 1e-3   # gamma, the selection bias's step (DeepSeek-V3 §4.2)
AXIS = "model"     # the mesh axis the held experts are divided over


def moe_init(key, cfg: ModelConfig, *, dtype=jnp.float32):
    """(params, state): the router, the held experts stacked, the shared
    experts; state holds the sigmoid router's selection bias."""
    d, f = cfg.d_model, cfg.moe_intermediate_size
    e, held = cfg.num_experts, cfg.experts_held
    kr, kg, ku, ko, ks = jax.random.split(key, 5)
    init = nn.fan_in_init()
    if cfg.act == "swiglu":
        experts = {
            "wi_gate": init(kg, (held, d, f), dtype),
            "wi_up": init(ku, (held, d, f), dtype),
            "wo": init(ko, (held, f, d), dtype),
        }
    else:
        experts = {"wi": init(kg, (held, d, f), dtype),
                   "wo": init(ko, (held, f, d), dtype)}
    p = {
        "router": nn.dense_init(kr, d, e, use_bias=False, dtype=dtype),
        "experts": experts,
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp.mlp_init(ks, cfg, dtype=dtype,
                                   d_ff=cfg.n_shared_experts * f)
    state = ({"router_bias": jnp.zeros((e,), jnp.float32)}
             if cfg.scoring_func == "sigmoid" else {})
    return p, state


def route(params, state, x: jax.Array, cfg: ModelConfig):
    """x: (T, d) -> (gates (T, k), expert ids (T, k), scores (T, E)) in
    float32, the router's product too (one bfloat16 pass, a TPU's default,
    would move near-tied selections).  Sigmoid routing selects the top-k of
    scores + bias and gates by the scores alone."""
    logits = jnp.dot(x.astype(jnp.float32),
                     params["router"]["kernel"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    k = cfg.top_k_experts
    if cfg.scoring_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        bias = jax.lax.stop_gradient(state["router_bias"])
        _, ids = jax.lax.top_k(scores + bias, k)
        gates = jnp.take_along_axis(scores, ids, axis=-1)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        gates, ids = jax.lax.top_k(scores, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-20)
    return gates * cfg.routed_scaling_factor, ids, scores


def balance_loss(scores, ids, cfg: ModelConfig, batch: int):
    """Switch's loss for softmax routing; for sigmoid routing DeepSeek-V3's
    sequence-wise loss sum_i f_i P_i (eqs. 17-20), averaged over the
    batch's sequences.  scores (T, E), ids (T, k), T = batch * seq."""
    e, k = cfg.num_experts, cfg.top_k_experts
    if cfg.scoring_func == "softmax":
        me = scores.mean(axis=0)
        ce = jax.nn.one_hot(ids[:, 0], e, dtype=jnp.float32).mean(axis=0)
        return e * jnp.sum(me * ce)
    s = scores.reshape(batch, -1, e)
    sel = jax.nn.one_hot(ids, e, dtype=jnp.float32).sum(-2)
    f = sel.reshape(batch, -1, e).mean(axis=1) * (e / k)
    p = (s / s.sum(-1, keepdims=True)).mean(axis=1)
    return jnp.mean(jnp.sum(jax.lax.stop_gradient(f) * p, axis=-1))


def _grouped(lhs, rhs, group_sizes, first):
    """Rows of group g (the copies sorted by group) times rhs[g - first]
    for the held groups; every other row is zero."""
    tiling = (_TILE, min(_TILE, lhs.shape[1]), min(_TILE, rhs.shape[2]))
    return megablox.gmm(lhs, rhs, group_sizes, jnp.float32, tiling,
                        jnp.asarray(first, jnp.int32), None, False,
                        interpret_mode()).astype(lhs.dtype)


def _experts(experts, xs, group_sizes, first, cfg: ModelConfig):
    """The held experts' FFN on the sorted copies xs (m, d) -> (m, d)."""
    if cfg.act == "swiglu":
        g = _grouped(xs, experts["wi_gate"], group_sizes, first)
        u = _grouped(xs, experts["wi_up"], group_sizes, first)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(xs.dtype) * u
    else:
        h = _grouped(xs, experts["wi"], group_sizes, first)
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(xs.dtype)
    return _grouped(h, experts["wo"], group_sizes, first)


def _held_part(experts, xf, gates, ids, first, cfg: ModelConfig):
    """The part of the experts in `experts` (groups first, first + 1, ...)
    for tokens xf (T, d) with their gates and expert ids (T, k)."""
    t, d = xf.shape
    k = cfg.top_k_experts
    with jax.named_scope("dispatch"):
        flat = ids.reshape(-1)
        load = jnp.zeros((cfg.num_experts,), jnp.int32).at[flat].add(1)
        order = jnp.argsort(flat, stable=True)
        pad = -flat.shape[0] % _TILE
        # a trailing group of padding rows, which no expert holds
        sizes = jnp.concatenate([load, jnp.full((1,), pad, jnp.int32)])
        src = jnp.concatenate([order // k, jnp.zeros((pad,), order.dtype)])
        xs = jnp.take(xf, src, axis=0)
    with jax.named_scope("experts"):
        ys = _experts(experts, xs, sizes, first, cfg)
    with jax.named_scope("combine"):
        back = jnp.argsort(order)        # copy (token, slot) -> sorted row
        yc = jnp.take(ys, back, axis=0).reshape(t, k, d)
        return jnp.einsum("tk,tkd->td", gates.astype(xf.dtype), yc)


def _routed(experts, xf, gates, ids, first, cfg: ModelConfig):
    """The held experts' part of the layer, on the ambient mesh's `model`
    axis where there is one (the module docstring)."""
    mesh = context.get_mesh()
    n = mesh.shape.get(AXIS, 1) if mesh is not None else 1
    if n == 1:
        return _held_part(experts, xf, gates, ids, first, cfg)
    # expert parallelism where the held experts divide the axis, else each
    # expert's hidden width split over it
    per = cfg.experts_held // n if cfg.experts_held % n == 0 else 0
    w_spec = {name: P(AXIS, None, None) if per else
              P(None, AXIS, None) if name == "wo" else P(None, None, AXIS)
              for name in experts}
    # tokens over the data-parallel axes, where they divide them
    batch = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    shards = int(np.prod([mesh.shape[a] for a in batch]))
    tok = P(batch if batch and xf.shape[0] % shards == 0 else None, None)

    def local(ex, xl, gl, il):
        start = first + jax.lax.axis_index(AXIS) * per
        return jax.lax.psum(_held_part(ex, xl, gl, il, start, cfg), AXIS)

    return shard_map(local, mesh=mesh, in_specs=(w_spec, tok, tok, tok),
                     out_specs=tok, check_vma=False)(experts, xf, gates, ids)


def moe_apply(params, state, x: jax.Array, cfg: ModelConfig, *,
              train: bool = False, first: int = 0):
    """x: (B, S, d) -> (y, aux_loss, new_state, counters).

    `first` is the first expert held here.  `counters`: `routed_copies`,
    the token copies the held experts multiplied, and `max_expert_load`,
    the most copies one held expert took."""
    b, s, d = x.shape
    e, held = cfg.num_experts, cfg.experts_held
    xf = x.reshape(b * s, d)
    with jax.named_scope("moe"):
        with jax.named_scope("route"):
            gates, ids, scores = route(params, state, xf, cfg)
            aux = balance_loss(scores, ids, cfg, b)
            load = jnp.zeros((e,), jnp.int32).at[ids.reshape(-1)].add(1)
            new_state = state
            if train and cfg.scoring_func == "sigmoid":
                step = jnp.sign(load.mean() - load.astype(jnp.float32))
                new_state = {"router_bias": state["router_bias"]
                             + BIAS_STEP * step}
        y = _routed(params["experts"], xf, gates, ids, first, cfg)
        if "shared" in params:
            with jax.named_scope("shared"):
                y = y + mlp.mlp_apply(params["shared"], xf, cfg)
    mine = jax.lax.dynamic_slice_in_dim(load, first, held)
    counters = {"routed_copies": mine.sum(),
                "max_expert_load": mine.max()}
    return y.reshape(b, s, d), aux, new_state, counters


def moe_apply_dense_reference(params, state, x: jax.Array,
                              cfg: ModelConfig, *, first: int = 0):
    """O(E)-compute oracle: every held expert on every token, masked by its
    gate, plus the shared experts.  Used only in tests."""
    b, s, d = x.shape
    held = cfg.experts_held
    xf = x.reshape(-1, d)
    gates, ids, _ = route(params, state, xf, cfg)
    dense_gates = jnp.zeros((xf.shape[0], cfg.num_experts), jnp.float32)
    dense_gates = dense_gates.at[
        jnp.arange(xf.shape[0])[:, None], ids].add(gates)
    mine = jax.lax.dynamic_slice_in_dim(dense_gates, first, held, axis=1)
    ex = params["experts"]
    xe = jnp.broadcast_to(xf, (held,) + xf.shape)
    if cfg.act == "swiglu":
        g = jnp.einsum("etd,edf->etf", xe, ex["wi_gate"])
        u = jnp.einsum("etd,edf->etf", xe, ex["wi_up"])
        h = jax.nn.silu(g) * u
    else:
        h = jax.nn.gelu(jnp.einsum("etd,edf->etf", xe, ex["wi"]))
    outs = jnp.einsum("etf,efd->etd", h, ex["wo"])
    y = jnp.einsum("te,etd->td", mine.astype(xf.dtype), outs)
    if "shared" in params:
        y = y + mlp.mlp_apply(params["shared"], xf, cfg)
    return y.reshape(b, s, d)
