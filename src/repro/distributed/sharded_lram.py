"""Model-parallel LRAM lookups: the `sharded` and `sharded-tiered`
placements of the lookup-plan registry (`repro.core.lookup`).

THE key TPU-native re-think of the paper's random-access memory (DESIGN.md
§3): the value table's rows are sharded over the `model` mesh axis.  Instead
of cross-chip random access (ruinous on TPU interconnects), every device

  1. receives the full (replicated-over-model) index/weight sets,
  2. gathers ONLY indices that fall inside its row shard (others masked to
     weight zero, index clamped),
  3. partially interpolates, and
  4. joins the partial outputs with a single psum over `model`.

Communication is O(tokens * heads * m) — *independent of N* — identical in
shape to a tensor-parallel FFN's reduce.  The O(1)-in-N property of the
paper survives sharding.  The backward pass (autodiff through shard_map)
scatter-adds only into local rows: value-table gradients never cross the
model axis at all.

Composition over the plan axes:

* **storage** — a `repro.quant.QuantizedTable` shards payload + per-row
  scales over the same axis; each device dequantizes only the rows it
  gathers locally, and the psum'd fp32 partials are unchanged —
  quantization is invisible to the collective.
* **kernel** — the shard-local gather can run the Pallas DMA gather
  kernel (`kernel="pallas"`; `repro.kernels.gather_interp`) instead of
  jnp take+einsum.  The custom-VJP wrappers keep the sparse backward
  contract inside `shard_map`.
* **tiering** — :class:`ShardedTieredStore` composes row-sharding with
  the host-offloaded tiered store: each model shard owns a contiguous
  row *range* backed by its own `TieredValueStore` (host shards + device
  hot cache), so the aggregate table can exceed any single host's
  memory.  Lookups route each index to its owning range, the ranges
  produce masked partial interpolations, and the partials are summed —
  the same partial-sum join as the dense sharded path (the psum, when
  ranges live on separate hosts).
"""

from __future__ import annotations

import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro import obs
from repro.core import lookup
from repro.distributed import context as _ctx
from repro.kernels import interpret_mode
from repro.memstore.store import TieredSpec, TieredValueStore
from repro.quant import QuantizedTable, dequantize_rows

AXIS = "model"


def sharded_gather_interp(mesh: Mesh, *, axis: str = AXIS,
                          kernel: str = "reference",
                          interpret: bool | None = None):
    """Returns an `interp_impl` hook (values, idx, w) -> out for lram_apply.

    values must be laid out P(axis, None); idx/w replicated along `axis`
    (they are functions of activations, which are batch-sharded on `data`).
    `values` may also be a `repro.quant.QuantizedTable`: its payload and
    per-row scales shard over the same axis, each device dequantizes only
    the rows it gathers locally, and the psum'd partials are unchanged —
    quantization is invisible to the collective.

    `kernel` selects the shard-local gather: "reference" (jnp) or
    "pallas" (`repro.kernels.gather_interp`, differentiable wrappers).
    """
    if kernel not in ("reference", "pallas"):
        raise ValueError(f"unknown kernel {kernel!r}")
    n_shards = mesh.shape[axis]
    other = tuple(a for a in mesh.axis_names if a != axis)
    act_spec = P(other if len(other) > 1 else (other[0] if other else None))
    if interpret is None:
        interpret = interpret_mode()

    def interp(values, idx, w):
        quantized = isinstance(values, QuantizedTable)
        table = values.q if quantized else values
        rows_local = table.shape[0] // n_shards

        def local_rows(values_l, idx_l):
            base = jax.lax.axis_index(axis) * rows_local
            rel = idx_l - base
            ok = (rel >= 0) & (rel < rows_local)
            rel_safe = jnp.clip(rel, 0, rows_local - 1)
            return rel_safe, ok

        def local(values_l, idx_l, w_l):
            rel_safe, ok = local_rows(values_l, idx_l)
            wm = w_l * ok.astype(w_l.dtype)
            if kernel == "pallas":
                from repro.kernels import gather_interp as gi

                out = gi.gather_interp_vjp(values_l, rel_safe, wm, interpret)
            else:
                rows = jnp.take(values_l, rel_safe, axis=0).astype(w_l.dtype)
                out = jnp.einsum("...k,...km->...m", wm, rows)
            return jax.lax.psum(out, axis)

        def local_quant(values_l, scale_l, idx_l, w_l):
            rel_safe, ok = local_rows(values_l, idx_l)
            wm = w_l * ok.astype(w_l.dtype)
            if kernel == "pallas":
                from repro.kernels import gather_interp as gi

                out = gi.gather_interp_quant(
                    values_l, scale_l, rel_safe, wm, interpret
                )
            else:
                rows = dequantize_rows(  # in-shard dequant, fp32 partials
                    jnp.take(values_l, rel_safe, axis=0),
                    jnp.take(scale_l, rel_safe, axis=0),
                ).astype(w_l.dtype)
                out = jnp.einsum("...k,...km->...m", wm, rows)
            return jax.lax.psum(out, axis)

        dim_spec = act_spec[0] if len(act_spec) else None
        io_spec = P(*((dim_spec,) + (None,) * (idx.ndim - 1)))
        if quantized:
            return shard_map(
                local_quant,
                mesh=mesh,
                in_specs=(P(axis, None), P(axis), io_spec, io_spec),
                out_specs=io_spec,
                check_vma=False,
            )(values.q, values.scale, idx, w)
        return shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axis, None), io_spec, io_spec),
            out_specs=io_spec,
            check_vma=False,
        )(values, idx, w)

    return interp


# ---------------------------------------------------------------------------
# sharded × tiered: per-model-shard host-offloaded row ranges
# ---------------------------------------------------------------------------

class ShardedTieredStore:
    """A row-range-sharded tiered table: `num_ranges` host-offloaded
    `TieredValueStore`s, each owning `num_rows / num_ranges` consecutive
    rows with its own device hot cache.

    This is the composition the old callable-hook protocol could not
    express: the *capacity* axis of tiering (table larger than HBM — and,
    across ranges, larger than any single host) under the *ownership*
    layout of model sharding (each shard's write-back, checkpoint
    streaming, and fills touch only its local range).  Lookups route each
    (index, weight) element to its owning range; every range contributes a
    masked partial interpolation and the partials are summed — exactly the
    psum join of the dense sharded path when ranges live on separate
    hosts (here they share one process, so the sum is local).

    Presents the same surface as `TieredValueStore` everywhere the rest
    of the repo cares: `gather` / `gather_rows_host` / `apply_writeback`
    for the lookup (so `repro.memstore.tiered_interp` drives it
    unchanged, eager and traced), `prefetch* / warm / flush / stats` for
    the serve engine and trainer, and the shard-streaming checkpoint
    interface with *global* shard ids (`shard_host(i)` etc.), which makes
    a sharded-tiered checkpoint byte-compatible with a plain tiered one
    of the same total layout — restore converts freely between the two.
    """

    def __init__(self, num_rows: int, m: int, spec: TieredSpec,
                 num_ranges: int, *, dtype=np.float32):
        if num_ranges < 1:
            raise ValueError("need at least one row range")
        if num_rows % num_ranges:
            raise ValueError(
                f"num_rows={num_rows} not divisible by "
                f"num_ranges={num_ranges}"
            )
        rows_local = num_rows // num_ranges
        if rows_local % spec.shard_rows:
            raise ValueError(
                f"range size {rows_local} not divisible by "
                f"shard_rows={spec.shard_rows}"
            )
        self.spec = spec
        self.num_rows = num_rows
        self.m = m
        self.num_ranges = num_ranges
        self.rows_local = rows_local
        self.quant = spec.quant
        self.shard_rows = spec.shard_rows
        self.dtype = np.dtype(dtype)
        self.parts = [
            TieredValueStore(rows_local, m, self._part_spec(spec, r),
                             dtype=dtype)
            for r in range(num_ranges)
        ]
        self._shards_per_range = self.parts[0].num_shards
        self.num_shards = num_ranges * self._shards_per_range
        self._traced_interp = None  # built lazily by repro.memstore.interp
        self._pool: ThreadPoolExecutor | None = None  # prefetch executor

    @staticmethod
    def _part_spec(spec: TieredSpec, r: int) -> TieredSpec:
        # mmap backings need one directory per range (the store's file
        # name encodes only rows x m, identical across ranges)
        if spec.backing == "mmap" and spec.backing_dir is not None:
            return dataclasses.replace(
                spec, backing_dir=os.path.join(spec.backing_dir, f"range_{r:03d}")
            )
        return spec

    @classmethod
    def from_dense(cls, values: np.ndarray, spec: TieredSpec,
                   num_ranges: int, **kw) -> "ShardedTieredStore":
        values = np.asarray(values)
        n, m = values.shape
        dtype = values.dtype if spec.quant == "none" else np.float32
        store = cls(n, m, spec, num_ranges, dtype=dtype, **kw)
        for r, part in enumerate(store.parts):
            lo = r * store.rows_local
            part._fill_host(values[lo:lo + store.rows_local])
        return store

    # ------------------------------------------------------------- routing

    def _route(self, flat_idx: np.ndarray):
        """Yields (part, selection mask, local indices) for every range
        the flat global ids touch."""
        for r, part in enumerate(self.parts):
            lo = r * self.rows_local
            sel = (flat_idx >= lo) & (flat_idx < lo + self.rows_local)
            if sel.any():
                yield part, sel, (flat_idx[sel] - lo).astype(np.int64)

    # ------------------------------------------------------------- lookups

    def gather(self, idx, w) -> jax.Array:
        """sum_k w[..., k] * values[idx[..., k]] -> (..., m): per-range
        masked partial interpolations, summed (the local form of the
        sharded psum join).  Each range's partial runs through its own
        device cache — misses fill, overflow serves host-side, exactly as
        in the single-range tiered store."""
        idx_np = np.asarray(idx)
        lead, top_k = idx_np.shape[:-1], idx_np.shape[-1]
        flat = idx_np.reshape(-1)
        w_flat = np.asarray(w, np.float32).reshape(-1)
        tokens = flat.size // top_k
        token_of = np.arange(flat.size) // top_k
        out = np.zeros((tokens, self.m), np.float32)
        for part, sel, local in self._route(flat):
            # k=1 sub-gather per routed element; scatter-add into the
            # owning token's output row.  The sub-batch is padded to a
            # power-of-two bucket (weight-0 repeats of an in-range row, so
            # no extra shard is touched): the jitted device gather then
            # sees O(log batch) distinct shapes, not one compile per
            # distinct routed-element count.
            n = local.size
            pad = 1 << max(0, n - 1).bit_length()
            idx_pad = np.full(pad, local[0], np.int32)
            idx_pad[:n] = local
            w_pad = np.zeros(pad, np.float32)
            w_pad[:n] = w_flat[sel]
            # valid_elems: the weight-0 tail must not count as accesses
            partial = part.gather(
                idx_pad.reshape(-1, 1), w_pad.reshape(-1, 1), valid_elems=n
            )
            np.add.at(out, token_of[sel], np.asarray(partial)[:n])
        return jnp.asarray(out.reshape(*lead, self.m))

    def gather_rows_host(self, idx) -> np.ndarray:
        """values[idx] -> (idx.shape + (m,)) fp32 via each range's host
        cache mirror — the io_callback body of the traced lookup."""
        idx_np = np.asarray(idx)
        flat = idx_np.reshape(-1)
        rows = np.empty((flat.size, self.m), np.float32)
        for part, sel, local in self._route(flat):
            rows[sel] = part.gather_rows_host(local)
        return rows.reshape(*idx_np.shape, self.m)

    # ------------------------------------------------------------ training

    @property
    def writeback_lr(self) -> float:
        return self.parts[0].writeback_lr

    @writeback_lr.setter
    def writeback_lr(self, lr: float) -> None:
        for part in self.parts:
            part.writeback_lr = lr

    def apply_writeback(self, idx, wg) -> None:
        """Sparse SGD write-back, routed: each range applies only the
        updates for rows it owns (value gradients never cross ranges)."""
        idx_np = np.asarray(idx)
        flat = idx_np.reshape(-1)
        upd = np.asarray(wg, np.float32).reshape(-1, self.m)
        for part, sel, local in self._route(flat):
            part.apply_writeback(local, upd[sel])

    # -------------------------------------------------- cache management
    # Range fills overlap each other through a small thread pool: each
    # range owns disjoint state (its own host shards, cache mirror, LRU),
    # so per-range prefetches are embarrassingly parallel — the serve
    # thread no longer serialises R host-memcpy walks.  Stat counting is
    # unchanged: prefetch never touches hit/miss counters, and fills are
    # counted inside each part exactly as on the serial path.

    def _executor(self) -> ThreadPoolExecutor:
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=min(8, self.num_ranges),
                thread_name_prefix="memstore-prefetch",
            )
        return self._pool

    def _fanout(self, calls) -> None:
        """Run (fn, kwargs) pairs, overlapped when there is more than one."""
        calls = list(calls)
        obs.gauge("memstore.prefetch_queue_depth").set(len(calls))
        if len(calls) <= 1:
            for fn, kw in calls:
                fn(**kw)
            return
        futs = [self._executor().submit(fn, **kw) for fn, kw in calls]
        for f in futs:
            f.result()

    def prefetch(self, idx, *, sync_device: bool = True) -> None:
        flat = np.asarray(idx).reshape(-1)
        self._fanout(
            (part.prefetch, dict(idx=local, sync_device=sync_device))
            for part, sel, local in self._route(flat)
        )

    def prefetch_last(self, *, sync_device: bool = False) -> None:
        self._fanout(
            (part.prefetch_last, dict(sync_device=sync_device))
            for part in self.parts
        )

    def warm(self, shards: Iterable[int] | None = None) -> None:
        if shards is None:
            for part in self.parts:
                part.warm()
            return
        per = self._shards_per_range
        for i in shards:
            self.parts[i // per].warm([i % per])

    def flush(self) -> None:
        for part in self.parts:
            part.flush()

    # --------------------------------------------------------------- stats

    @property
    def stats(self) -> dict:
        agg: dict = {}
        for part in self.parts:
            for k, v in part.stats.items():
                agg[k] = agg.get(k, 0) + v
        return agg

    def reset_stats(self) -> None:
        for part in self.parts:
            part.reset_stats()

    def hit_rate(self) -> float:
        s = self.stats
        total = s["hits"] + s["misses"] + s["uncached"]
        return s["hits"] / total if total else 0.0

    def row_stats(self) -> tuple[np.ndarray, int]:
        """(per-shard access counts in global shard order, rows per shard):
        ranges are row-contiguous, so concatenating per-part counters IS
        the global shard axis (the checkpoint stream's shard order)."""
        return (np.concatenate([p.shard_access for p in self.parts]),
                self.shard_rows)

    # ------------------------------------------------------------- lifecycle

    def _read_rows_raw(self, rows: np.ndarray):
        """(payload, scales|None) for global row ids in storage form —
        routed to the owning ranges; see TieredValueStore._read_rows_raw."""
        flat = np.asarray(rows, np.int64).reshape(-1)
        if flat.size and (flat.min() < 0 or flat.max() >= self.num_rows):
            # an unrouted id would leave np.empty rows uninitialized
            raise ValueError("row ids must index the table")
        payload = np.empty((flat.size, self.m),
                           self.parts[0].storage_dtype
                           if self.quant != "none" else self.parts[0].dtype)
        scales = (np.empty(flat.size, np.float32)
                  if self.quant != "none" else None)
        for part, sel, local in self._route(flat):
            p, s = part._read_rows_raw(local)
            payload[sel] = p
            if scales is not None:
                scales[sel] = s
        return payload, scales

    def grow_rows(self, new_num_rows: int, parents: np.ndarray) -> None:
        """Append rows [num_rows, new_num_rows) as *new ranges* — in place.

        Existing ranges keep their row spans, host shards, and device
        caches untouched (the same append-only property as
        `TieredValueStore.grow_rows`); each appended range is a fresh
        tiered store of `rows_local` rows whose host tier is filled from
        the parent rows, inheriting the live `writeback_lr`.  Global shard
        ids extend contiguously, so grown checkpoints stay
        byte-compatible with plain tiered stores of the same layout.
        """
        delta = new_num_rows - self.num_rows
        if delta <= 0 or delta % self.rows_local:
            raise ValueError(
                f"new_num_rows={new_num_rows} must exceed {self.num_rows} "
                f"by a multiple of the range size {self.rows_local}"
            )
        parents = np.asarray(parents, np.int64).reshape(-1)
        if parents.size != delta:
            raise ValueError(f"need {delta} parent rows, got {parents.size}")
        if parents.size and (parents.min() < 0
                             or parents.max() >= self.num_rows):
            raise ValueError("parent row ids must index the old table")
        payload, scales = self._read_rows_raw(parents)
        lr = self.writeback_lr
        for k in range(delta // self.rows_local):
            r = self.num_ranges + k
            part = TieredValueStore(
                self.rows_local, self.m, self._part_spec(self.spec, r),
                dtype=self.dtype,
            )
            lo = k * self.rows_local
            pay3 = payload[lo:lo + self.rows_local].reshape(
                part.num_shards, part.shard_rows, self.m
            )
            part._host[...] = pay3
            if self.quant != "none":
                part._host_scale[...] = scales[
                    lo:lo + self.rows_local
                ].reshape(part.num_shards, part.shard_rows)
            part.writeback_lr = lr
            self.parts.append(part)
        self.num_rows = new_num_rows
        self.num_ranges = len(self.parts)
        self.num_shards = self.num_ranges * self._shards_per_range
        if self._pool is not None:  # resize the executor to the new fanout
            self._pool.shutdown(wait=False)
            self._pool = None

    def bytes_per_entry(self) -> int:
        return self.parts[0].bytes_per_entry()

    def resident_shards(self) -> list[int]:
        per = self._shards_per_range
        return [r * per + s
                for r, part in enumerate(self.parts)
                for s in part.resident_shards()]

    # ---------------------------------------------------------- checkpoint
    # global shard ids: shard i lives in range i // shards_per_range —
    # the on-disk stream is identical to a tiered store of the same
    # (num_shards, shard_rows, m), so tiered <-> sharded-tiered restore
    # is free (repro.checkpoint).

    def shard_host(self, i: int) -> np.ndarray:
        per = self._shards_per_range
        return self.parts[i // per].shard_host(i % per)

    def shard_scale_host(self, i: int) -> np.ndarray:
        per = self._shards_per_range
        return self.parts[i // per].shard_scale_host(i % per)

    def load_shard(self, i: int, arr: np.ndarray,
                   scale: np.ndarray | None = None) -> None:
        per = self._shards_per_range
        self.parts[i // per].load_shard(i % per, arr, scale)

    def load_dense(self, values: np.ndarray) -> None:
        values = np.asarray(values)
        if values.shape != (self.num_rows, self.m):
            raise ValueError(
                f"shape {values.shape} != {(self.num_rows, self.m)}"
            )
        for r, part in enumerate(self.parts):
            lo = r * self.rows_local
            part.load_dense(values[lo:lo + self.rows_local])

    def to_dense(self) -> np.ndarray:
        return np.concatenate([part.to_dense() for part in self.parts])

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (
            f"ShardedTieredStore(rows={self.num_rows}, m={self.m}, "
            f"ranges={self.num_ranges}x{self.rows_local}, "
            f"quant={self.quant!r}, hit_rate={self.hit_rate():.3f})"
        )


# Leafless pytree node, like TieredValueStore: rides params untouched.
jax.tree_util.register_pytree_node(
    ShardedTieredStore,
    lambda s: ((), s),
    lambda aux, children: aux,
)
lookup.register_store_type(ShardedTieredStore)


# ---------------------------------------------------------------------------
# placement backends (repro.core.lookup registry)
# ---------------------------------------------------------------------------

def _sharded_factory(cfg, storage: str, kernel: str) -> lookup.LookupPlan:
    mesh = _ctx.get_mesh()
    if mesh is None or AXIS not in mesh.axis_names:
        raise lookup.LookupPlanError(
            "sharded", storage, kernel,
            f"needs an ambient mesh with a {AXIS!r} axis — call "
            "repro.distributed.context.set_mesh(mesh) before resolving",
        )
    n_shards = mesh.shape[AXIS]
    if cfg.num_locations % n_shards:
        raise lookup.LookupPlanError(
            "sharded", storage, kernel,
            f"num_locations={cfg.num_locations} not divisible by the "
            f"{AXIS!r} axis size {n_shards}",
        )
    hook = sharded_gather_interp(mesh, axis=AXIS, kernel=kernel)

    if storage == "fp32":
        def build_table(dense):
            return dense

        def interp(values, idx, w):
            if lookup.is_store(values) or isinstance(values, QuantizedTable):
                raise lookup.LookupPlanError(
                    "sharded", storage, kernel,
                    f"expected a dense fp32 table, got "
                    f"{type(values).__name__}",
                )
            return hook(values, idx, w)

        return lookup.LookupPlan(
            placement="sharded", storage=storage, kernel=kernel,
            build_table=build_table, interp=interp, requires_mesh=True,
            # growing a mesh-sharded dense table means resharding live
            # device buffers — a relaunch (or a migration to
            # sharded-tiered) is the supported path
            supports_growth=False, table_rows_axis=AXIS,
        )

    def build_table_q(dense):
        return QuantizedTable.from_dense(dense, storage)

    def interp_q(values, idx, w):
        if not isinstance(values, QuantizedTable):
            raise lookup.LookupPlanError(
                "sharded", storage, kernel,
                f"expected a QuantizedTable, got {type(values).__name__}",
            )
        return hook(values, idx, w)

    return lookup.LookupPlan(
        placement="sharded", storage=storage, kernel=kernel,
        build_table=build_table_q, interp=interp_q,
        table_update="frozen", requires_mesh=True,
        supports_growth=False, table_rows_axis=AXIS,
    )


def _sharded_tiered_factory(cfg, storage: str,
                            kernel: str) -> lookup.LookupPlan:
    spec = lookup.merged_tiered_spec(cfg, storage, kernel)
    mesh = _ctx.get_mesh()
    num_ranges = cfg.model_shards
    if num_ranges <= 0:
        num_ranges = (mesh.shape[AXIS]
                      if mesh is not None and AXIS in mesh.axis_names else 1)
    if cfg.num_locations % num_ranges:
        raise lookup.LookupPlanError(
            "sharded-tiered", storage, kernel,
            f"num_locations={cfg.num_locations} not divisible by "
            f"model_shards={num_ranges}",
        )
    if (cfg.num_locations // num_ranges) % spec.shard_rows:
        raise lookup.LookupPlanError(
            "sharded-tiered", storage, kernel,
            f"range size {cfg.num_locations // num_ranges} not divisible "
            f"by TieredSpec.shard_rows={spec.shard_rows}",
        )

    def build_table(dense):
        return ShardedTieredStore.from_dense(
            np.asarray(dense), spec, num_ranges
        )

    def interp(values, idx, w):
        if not isinstance(values, ShardedTieredStore):
            raise lookup.LookupPlanError(
                "sharded-tiered", storage, kernel,
                "params['values'] must be a ShardedTieredStore — init the "
                "layer with LRAMConfig(interp_impl='sharded-tiered')",
            )
        from repro.memstore import tiered_interp

        return tiered_interp(values, idx, w)

    return lookup.LookupPlan(
        placement="sharded-tiered", storage=storage, kernel=kernel,
        build_table=build_table, interp=interp,
        supports_prefetch=True, table_update="writeback",
        checkpoint_layout="shards",
        supports_growth=True, row_stats=True,
        build_empty=lambda: ShardedTieredStore(
            cfg.num_locations, cfg.m, spec, num_ranges
        ),
        # single-process row-range store: base rows are host-readable, so
        # the per-tenant overlay composes (the mesh-sharded dense plan
        # above stays overlay-free: its rows live in device shards)
        supports_overlay=True,
    )


lookup.register_placement("sharded", _sharded_factory)
lookup.register_placement("sharded-tiered", _sharded_tiered_factory)
