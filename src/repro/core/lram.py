"""LRAM: the lattice-based differentiable random-access memory layer.

Composition (paper §2.3, §3.1):

    x (..., 2*h*8) --per-head query norm--> torus_map --> q (..., h, 8)
      --E8 neighbor enumeration--> top-32 (index, weight) pairs
      --gather from shared value table (N, m), weighted sum, scale-->
    y (..., h*m)

plus the memory-augmented FFN block that replaces a transformer FFN:
dense(w -> w) . LRAM(w -> 4w, (n,m,h)=(8,64,w/16)) . dense(4w -> w).

The lookup is O(1) in N: per query it touches 232 candidate rows of a fixed
table (one 8x232 MXU matmul) and gathers top_k=32 value rows.  Gradients are
input-dependent-sparse: dL/dvalues has at most 32*h nonzero rows per token
(autodiff of the gather produces exactly the scatter-add the paper's CUDA
backward implements).

Implementation selection is a **plan** over three orthogonal axes
(`repro.core.lookup`): placement (`LRAMConfig.interp_impl` — dense |
tiered | sharded | sharded-tiered, with "reference"/"pallas" as dense
aliases), storage (`LRAMConfig.table_quant` — fp32 | int8 | fp8 rows with
per-row scales, `repro.quant`), and kernel (`LRAMConfig.lookup_kernel` —
jnp reference or the Pallas DMA gather kernels).  The plan is
resolved once at `lram_init`/trace time; it builds the value table
(`params["values"]` — a dense array, `QuantizedTable`,
`TieredValueStore`, or `ShardedTieredStore`) and owns the gather+interp
step with its autodiff contract.  `lram_apply`'s `interp_impl` argument
overrides the config's placement per call (an impl name string; the legacy
callable-hook protocol was removed — register a placement backend instead).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro import nn
from repro.core import indexing, lattice, lookup, overlay, torus


@dataclasses.dataclass(frozen=True)
class LRAMConfig:
    log2_locations: int = 18  # N = 2**18 == paper's LRAM-small
    # explicit wrap lengths (indexing.TorusSpec) — set by memctl.grow:
    # grown configs carry the index-preserving K_0-enlarged torus instead
    # of the near-cubic choose_torus default.  None = choose_torus.
    torus: Any = None
    m: int = 64               # value dim per head (paper: 64)
    heads: int = 32           # h; layer input dim = 16*h, output = m*h
    top_k: int = 32           # paper §2.6: top-32 carries >=99.5% of mass
    query_norm: str = "batch"  # batch | rms | none  (paper: batchnorm)
    value_init_scale: float = 0.02
    table_dtype: str = "float32"
    # --- the lookup plan's three axes (repro.core.lookup) ---
    interp_impl: str = "reference"  # placement: reference/pallas (dense) |
    #                                 tiered | sharded | sharded-tiered
    tiered: Any = None              # memstore.TieredSpec for tiered placements
    table_quant: str = "none"       # storage: none | int8 | fp8
    lookup_kernel: str = "auto"     # kernel: auto | reference | pallas
    model_shards: int = 0           # sharded-tiered row-range owners
    #                                 (0 = ambient mesh's model-axis size)

    def __post_init__(self):
        if self.table_quant not in ("none", "int8", "fp8"):
            raise ValueError(
                f"table_quant must be none|int8|fp8, got {self.table_quant!r}"
            )
        if self.torus is not None \
                and self.torus.num_locations != 2**self.log2_locations:
            raise ValueError(
                f"torus has {self.torus.num_locations} locations but "
                f"log2_locations={self.log2_locations}"
            )

    @property
    def torus_spec(self) -> indexing.TorusSpec:
        if self.torus is not None:
            return self.torus
        return indexing.choose_torus(self.log2_locations)

    @property
    def num_locations(self) -> int:
        return 2**self.log2_locations

    @property
    def in_dim(self) -> int:
        return 2 * lattice.DIM * self.heads

    @property
    def out_dim(self) -> int:
        return self.m * self.heads

    @property
    def num_params(self) -> int:
        return self.num_locations * self.m

    @property
    def table_bytes_per_entry(self) -> int:
        """Storage bytes per table row (payload + per-row scale if quantized)."""
        from repro import quant

        if self.table_quant == "none":
            return self.m * jnp.dtype(self.table_dtype).itemsize
        return quant.bytes_per_entry(self.m, self.table_quant)


# ---------------------------------------------------------------------------
# Lookup primitives (reference path; the plan registry swaps the rest)
# ---------------------------------------------------------------------------

def indices_and_weights(
    q: jax.Array, spec: indexing.TorusSpec, top_k: int
) -> tuple[jax.Array, jax.Array]:
    """Top-k (lattice index, kernel weight) pairs for queries q (..., 8).

    Two equivalent selection strategies (tests assert identical results):

    * no mesh (host/tests): jax.lax.top_k — fastest single-device — over
      the canonical frame's weights, and only the top_k selected candidates
      are mapped back to global points.
    * under a mesh: top_k unrolled masked-argmax passes (mirroring the
      Pallas kernel).  XLA's sort-based top_k does not partition on
      non-sort dims and all-gathered the full 232-candidate tensor
      (87 GiB/step at pod scale — EXPERIMENTS.md §Perf cell 3);
      argmax/where/sum are trivially shard-local, and indices for all 232
      candidates are computed up front and selected by an exact integer
      one-hot reduction — no sorts, no gathers.

    Besides `neighbors_and_weights`' scopes (`decode`, `distance`,
    `undo_isometry`), the selection runs under the named scope `top_k` and
    the lattice-point encoding under `encode`."""
    from repro.distributed import context as _ctx

    if _ctx.get_mesh() is None:
        # host path: sort-based top_k is fastest on a single device
        c, perm, sgn, w = lattice.canonical_weights(q)
        cand = jnp.asarray(lattice.candidate_arrays()[0], dtype=q.dtype)
        with jax.named_scope("top_k"):
            w_top, sel = jax.lax.top_k(w, top_k)
            # the selected table rows (..., top_k, 8) as a one-hot product:
            # exact, and faster on a TPU than a gather of 8-wide rows
            cand_top = jnp.einsum(
                "...kj,jl->...kl",
                jax.nn.one_hot(sel, lattice.NUM_CANDIDATES, dtype=q.dtype),
                cand,
                precision=jax.lax.Precision.HIGHEST,
            )
        with jax.named_scope("undo_isometry"):
            nb_top = lattice.undo_isometry(cand_top, c, perm, sgn)
        with jax.named_scope("encode"):
            return indexing.encode_points(nb_top, spec), w_top
    nbrs, w = lattice.neighbors_and_weights(q)  # (...,232,8), (...,232)
    with jax.named_scope("encode"):
        idx_all = indexing.encode_points(nbrs, spec)  # (..., 232) int32
    with jax.named_scope("top_k"):
        iota = jax.lax.broadcasted_iota(jnp.int32, w.shape, w.ndim - 1)
        scores = w
        idxs, ws = [], []
        for _ in range(top_k):
            m = jnp.max(scores, axis=-1)
            am = jnp.argmax(scores, axis=-1)
            hit = iota == am[..., None]
            idxs.append(jnp.sum(jnp.where(hit, idx_all, 0), axis=-1))
            ws.append(m)
            scores = jnp.where(hit, -1.0, scores)
        return jnp.stack(idxs, axis=-1), jnp.stack(ws, axis=-1)


def gather_interp(values: jax.Array, idx: jax.Array, w: jax.Array) -> jax.Array:
    """sum_k w_k * values[idx_k]  -> (..., m).  Reference implementation."""
    rows = jnp.take(values, idx, axis=0).astype(w.dtype)  # (..., k, m)
    return jnp.einsum("...k,...km->...m", w, rows)


# ---------------------------------------------------------------------------
# The layer
# ---------------------------------------------------------------------------

def lram_init(key, cfg: LRAMConfig, *, dtype=jnp.float32):
    """Returns (params, state). State holds batchnorm running stats.

    The value table is built by the resolved lookup plan
    (`repro.core.lookup`): every placement starts from the *same* RNG
    draw, so a tiered/sharded/quantized layer is numerically identical to
    its dense fp32 twin at init up to storage rounding."""
    kv, _ = jax.random.split(key)
    plan = lookup.resolve(cfg)
    table_dtype = jnp.dtype(cfg.table_dtype)
    values = plan.build_table(
        nn.truncated_normal_init(cfg.value_init_scale)(
            kv, (cfg.num_locations, cfg.m), table_dtype
        )
    )
    params: dict[str, Any] = {"values": values}
    state: dict[str, Any] = {}
    if cfg.query_norm == "batch":
        params["qnorm"], state["qnorm"] = nn.batchnorm_init(
            2 * lattice.DIM, dtype=dtype
        )
    elif cfg.query_norm == "rms":
        params["qnorm"] = nn.rmsnorm_init(2 * lattice.DIM, dtype=dtype)
    return params, state


def lram_apply(
    params,
    state,
    x: jax.Array,
    cfg: LRAMConfig,
    *,
    train: bool = False,
    interp_impl: str | None = None,
    return_access: bool = False,
):
    """Apply the memory layer.

    Args:
      x: (..., 2*8*heads) inputs.
      interp_impl: optional placement override for the gather+interpolate
        step — an impl name ("reference" | "pallas" | "tiered" | "sharded"
        | "sharded-tiered"); defaults to cfg.interp_impl.  Resolution goes
        through `repro.core.lookup.resolve`, which raises
        `LookupPlanError` for unsupported cells (callables included: the
        legacy hook protocol was removed).
      return_access: additionally return (indices, weights) — used by the
        memory-utilisation analysis (paper Table 5).

    Returns:
      (y, new_state[, access]) with y: (..., heads*m).
    """
    if x.shape[-1] != cfg.in_dim:
        raise ValueError(f"LRAM expects {cfg.in_dim} features, got {x.shape}")
    plan = lookup.resolve(cfg, interp_impl)
    with jax.named_scope("lram"):
        y, new_state, idx, w = _lram_body(params, state, x, cfg, plan, train)
    if return_access:
        return y, new_state, (idx, w)
    return y, new_state


def _lram_body(params, state, x, cfg: LRAMConfig, plan, train: bool):
    """`lram_apply` under its `lram` scope.  Each phase has a scope of its
    own (`query_norm`, `query`, `gather`, `overlay`): compiled ops carry it
    in their `op_name` metadata, so a device trace is read by phase."""
    lead = x.shape[:-1]
    xh = x.reshape(*lead, cfg.heads, 2 * lattice.DIM)
    # heads ride the tensor-parallel axis (table shared/replicated): the
    # whole query->decode->gather pipeline then stays shard-local
    from repro.distributed import context as _ctx
    xh = _ctx.constrain(
        xh, *( (_ctx.batch_axes(),) + (None,) * (len(lead) - 1)
               + ("model", None) )
    )
    new_state = dict(state)
    with jax.named_scope("query_norm"):
        if cfg.query_norm == "batch":
            xh, new_state["qnorm"] = nn.batchnorm(
                params["qnorm"], state["qnorm"], xh, train=train
            )
        elif cfg.query_norm == "rms":
            xh = nn.rmsnorm(params["qnorm"], xh)

    spec = cfg.torus_spec
    with jax.named_scope("query"):
        q, scale = torus.torus_map(xh.astype(jnp.float32), spec.K)
        idx, w = indices_and_weights(q, spec, cfg.top_k)
    with jax.named_scope("gather"):
        out = plan.interp(params["values"], idx, w)
    # per-tenant overlay (serve engine): correct rows the tenant has
    # overwritten, and record the access for the decode-step writeback.
    # Trace-time only — `current()` is None outside an engine overlay
    # context, and jit never re-runs this Python on cached calls.
    octx = overlay.current()
    if octx is not None:
        with jax.named_scope("overlay"):
            out = octx.apply(idx, w, out)
    # (..., heads, m)
    out = out * scale
    if octx is not None:
        with jax.named_scope("overlay"):
            octx.record(idx, w, out)
    y = out.reshape(*lead, cfg.out_dim).astype(x.dtype)
    return y, new_state, idx, w


# ---------------------------------------------------------------------------
# Memory-augmented FFN block (paper §3.1)
# ---------------------------------------------------------------------------

def memffn_config(width: int, log2_locations: int, **kw) -> LRAMConfig:
    """The paper's block shape: (n, m, h) = (8, 64, w/16)."""
    if width % 16 != 0:
        raise ValueError("width must be divisible by 16")
    return LRAMConfig(
        log2_locations=log2_locations, m=64, heads=width // 16, **kw
    )


def memffn_init(key, width: int, cfg: LRAMConfig, *, dtype=jnp.float32):
    if cfg.in_dim != width or cfg.out_dim != 4 * width:
        raise ValueError("cfg does not match the paper block shape")
    # NOTE: earlier revisions reused k1 for both lram_init and wi (k2 was
    # split but never consumed), correlating the memory table with the
    # input projection.  Seeding wi from k2 decorrelates them — an
    # intentional init-behaviour change: checkpoints are unaffected, but
    # fresh inits of this block differ from pre-fix runs.
    k1, k2, k3 = jax.random.split(key, 3)
    lram_params, lram_state = lram_init(k1, cfg, dtype=dtype)
    params = {
        "wi": nn.dense_init(k2, width, width, dtype=dtype),
        "lram": lram_params,
        "wo": nn.dense_init(k3, 4 * width, width, dtype=dtype),
    }
    return params, {"lram": lram_state}


def memffn_apply(
    params,
    state,
    x: jax.Array,
    cfg: LRAMConfig,
    *,
    train: bool = False,
    interp_impl: str | None = None,
):
    h = nn.dense(params["wi"], x)
    h, lram_state = lram_apply(
        params["lram"], state["lram"], h, cfg, train=train,
        interp_impl=interp_impl,
    )
    y = nn.dense(params["wo"], h)
    return y, {"lram": lram_state}


def flop_count(width: int, tokens: int) -> int:
    """Paper Table 3: ~(5/4)*r*w^2 MACs/token with r=4 — independent of N."""
    dense_flops = 2 * tokens * (width * width + 4 * width * width)
    lookup_flops = 2 * tokens * (width // 16) * (
        8 * lattice.NUM_CANDIDATES  # distance matmul
        + lattice.DEFAULT_TOP_K * 64  # interpolation
    )
    return dense_flops + lookup_flops
