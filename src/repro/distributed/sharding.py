"""GSPMD partition rules: hybrid FSDP(data) x TP(model) x EP, MaxText-style.

Param placement is decided by regex match on the flattened tree path; the
matched spec describes the *trailing* dims (scanned segments carry leading
layer/unit dims, padded with None).  On the multi-pod mesh the FSDP axis is
("pod", "data") — pods extend data parallelism; `model` stays intra-pod
(ICI-local), which is what keeps the collective roofline term sane: TP
collectives never cross the pod axis.

Divisibility-aware: any rule whose axis does not divide the dim falls back
to replication for that dim (e.g. kv_heads=2 cannot shard over model=16, so
decode caches shard head_dim instead — see cache_pspecs).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    fsdp: tuple[str, ...] = ("data",)
    tp: str = "model"

    @classmethod
    def for_mesh(cls, mesh: Mesh) -> "MeshAxes":
        if "pod" in mesh.axis_names:
            return cls(fsdp=("pod", "data"), tp="model")
        return cls(fsdp=("data",), tp="model")


def _rules(ax: MeshAxes) -> list[tuple[str, tuple]]:
    F, T = ax.fsdp, ax.tp
    return [
        # embeddings: vocab on TP, feature on FSDP
        (r"embed/embedding$", (T, F)),
        (r"pos_embed$", (None, None)),
        (r"enc_pos_embed$", (None, None)),
        (r"lm_head/kernel$", (F, T)),
        # attention
        (r"(attn|cross)/wq/kernel$", (F, T)),
        (r"(attn|cross)/wk/kernel$", (F, T)),
        (r"(attn|cross)/wv/kernel$", (F, T)),
        (r"(attn|cross)/wo/kernel$", (T, F)),
        (r"(attn|cross)/w[qkv]/bias$", (T,)),
        # dense mlp
        (r"(mlp|moe/shared)/wi(_gate|_up)?/kernel$", (F, T)),
        (r"(mlp|moe/shared)/wo/kernel$", (T, F)),
        (r"mlp/w[io].*?/bias$", (None,)),
        # MoE: experts on TP axis (expert parallelism) when E divides the
        # axis; otherwise Megatron-style TP *within* each expert (hidden dim
        # column/row sharded, one psum per layer).  The naive fallback
        # (replicate E, FSDP the contracting dim) produced a 42 TiB/step
        # all-reduce on mixtral (E=8 < model=16) — see EXPERIMENTS.md §Perf.
        (r"moe/router/kernel$", (F, None)),
        (r"moe/experts/wi(_gate|_up)?$", [(T, F, None), (None, F, T)]),
        (r"moe/experts/wi$", [(T, F, None), (None, F, T)]),
        (r"moe/experts/wo$", [(T, None, F), (None, T, F)]),
        # mamba
        (r"mamba/in_proj/kernel$", (F, T)),
        (r"mamba/out_proj/kernel$", (T, F)),
        (r"mamba/conv$", (None, T)),
        (r"mamba/(A_log|D|dt_bias)$", (None,)),
        (r"mamba/norm/scale$", (T,)),
        # LRAM memory tables carry NO rule here: the resolved LookupPlan
        # emits their placement directly (`table_rows_axis` —
        # `_memory_table_spec` below).  Dense plans replicate the table +
        # shard heads on TP, exactly a TP-FFN's collective shape
        # (EXPERIMENTS.md §Perf cell 3); the sharded plan rows-shards it
        # over `model`; tiered plans keep it host-side (leafless).
        (r"pkm/values$", (T, None)),
        (r"pkm/subkeys[12]$", (None, T, None)),
        (r"pkm/query/kernel$", (F, T)),
        (r"memffn/wi/kernel$", (F, T)),
        (r"memffn/wo/kernel$", (T, F)),
        # norms, biases, batchnorm state: replicated
        (r".*", None),
    ]


def _apply_spec(spec: tuple, ndim: int, shape, mesh: Mesh):
    """Left-pad for stacked (scan) leading dims + per-dim divisibility."""
    spec = (None,) * (ndim - len(spec)) + tuple(spec)
    fixed, clean = [], True
    for dim, s in zip(shape, spec):
        if s is None:
            fixed.append(None)
            continue
        axes = (s,) if isinstance(s, str) else tuple(s)
        size = int(np.prod([mesh.shape[a] for a in axes]))
        if dim % size == 0:
            fixed.append(s)
        else:
            fixed.append(None)
            clean = False
    return P(*fixed), clean


def _spec_for(name: str, ndim: int, shape, mesh: Mesh,
              ax: MeshAxes) -> P:
    for pat, spec in _rules(ax):
        if re.search(pat, name):
            if spec is None:
                return P()
            candidates = spec if isinstance(spec, list) else [spec]
            best = None
            for cand in candidates:
                p, clean = _apply_spec(cand, ndim, shape, mesh)
                if best is None:
                    best = p
                if clean:
                    return p
            return best
    return P()


def _memory_table_spec(plan, ndim: int, shape, mesh: Mesh) -> P:
    """The LRAM value table's pspec, emitted by its resolved LookupPlan:
    `table_rows_axis` names the mesh axis the leading (row) axis shards
    over (None = replicate).  Applies uniformly to every table leaf — the
    fp32 array (N, m), a QuantizedTable's payload (N, m), and its per-row
    scales (N,) — since all of them are row-major over the same N."""
    axis = plan.table_rows_axis
    if axis is None or axis not in mesh.axis_names:
        return P()
    spec, _ = _apply_spec(
        (axis,) + (None,) * (ndim - 1), ndim, shape, mesh
    )
    return spec


def param_pspecs(params, mesh: Mesh,
                 ax: Optional[MeshAxes] = None, *, model_cfg=None):
    """Pytree of PartitionSpec mirroring `params`.

    `model_cfg` (a ModelConfig) lets the resolved lookup plan place the
    memory tables (`lram/values` leaves) instead of a path-regex rule —
    required for row-sharded tables (`interp_impl="sharded"`), harmless
    otherwise (dense plans replicate, matching the regex-era default)."""
    ax = ax or MeshAxes.for_mesh(mesh)
    mem_plan = None
    if model_cfg is not None:
        from repro.core import lookup

        plans = lookup.model_plans(model_cfg)
        mem_plan = plans[0] if plans else None
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat:
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        if mem_plan is not None and "lram/values" in name:
            specs.append(
                _memory_table_spec(mem_plan, leaf.ndim, leaf.shape, mesh)
            )
            continue
        specs.append(_spec_for(name, leaf.ndim, leaf.shape, mesh, ax))
    return jax.tree_util.tree_unflatten(treedef, specs)


def shard_params(params, mesh: Mesh, *, model_cfg=None):
    specs = param_pspecs(params, mesh, model_cfg=model_cfg)
    return jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)), params, specs
    )


def batch_pspec(mesh: Mesh) -> P:
    """Input batches: global batch over (pod?, data)."""
    ax = MeshAxes.for_mesh(mesh)
    return P(ax.fsdp if len(ax.fsdp) > 1 else ax.fsdp[0])


def _shard_dim(dim: int, axis: str, mesh: Mesh):
    return axis if dim % mesh.shape[axis] == 0 else None


def cache_pspecs(cache_like, cfg, mesh: Mesh):
    """Decode-cache placement with divisibility fallbacks, keyed by the
    cache-entry name (structural, not shape-guessing):

      k/v/ck/cv  (..., B, T, Kh, D): B->data when divisible (else T->data,
                 the long_500k B=1 case); Kh->model, else D->model (low-kv
                 GQA archs: kv=2 cannot split 16 ways, head_dim=128 can).
      ssm        (..., B, H, N, P): B->data, H->model.
      conv       (..., B, W, C):    B->data, C->model.
    """
    ax = MeshAxes.for_mesh(mesh)
    data_ax = ax.fsdp[-1]
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_like)
    specs = []
    for path, leaf in flat:
        name = str(getattr(path[-1], "key", path[-1]))
        shape, nd = leaf.shape, leaf.ndim
        if name in ("k", "v", "ck", "cv"):
            b, t, kh, d = shape[-4], shape[-3], shape[-2], shape[-1]
            sb = _shard_dim(b, data_ax, mesh)
            st = _shard_dim(t, data_ax, mesh) if sb is None else None
            skh = _shard_dim(kh, ax.tp, mesh)
            sd = None if skh else _shard_dim(d, ax.tp, mesh)
            specs.append(P(*(None,) * (nd - 4), sb, st, skh, sd))
        elif name == "ssm":
            b, h = shape[-4], shape[-3]
            specs.append(P(
                *(None,) * (nd - 4),
                _shard_dim(b, data_ax, mesh),
                _shard_dim(h, ax.tp, mesh), None, None,
            ))
        elif name == "conv":
            b, c = shape[-3], shape[-1]
            specs.append(P(
                *(None,) * (nd - 3),
                _shard_dim(b, data_ax, mesh), None,
                _shard_dim(c, ax.tp, mesh),
            ))
        else:
            specs.append(P())
    return jax.tree_util.tree_unflatten(treedef, specs)
