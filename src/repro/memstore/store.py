"""Tiered value table: host-RAM (or disk) shards + a device-resident hot cache.

The dense `LRAM` keeps the whole (N, m) value table in device memory, which
caps N at HBM size long before the paper's "billions of entries".  This
module splits the table into fixed-size *shards* of `shard_rows` consecutive
lattice-bucket rows:

    global row id  r  ->  shard  r >> log2(shard_rows)
                          row    r &  (shard_rows - 1)

  * **Host tier** — one `(num_shards, shard_rows, m)` ndarray in host RAM
    (`backing="ram"`), or an `np.memmap`-backed ``.npy`` on disk
    (`backing="mmap"`) for tables larger than host memory.
  * **Device tier** — `cache_slots` shard-sized slots in device memory plus
    an *indirection table* `shard -> slot` (-1 = not resident).  Lookups map
    (shard, row) through the indirection table and gather from the cache
    with a single device kernel (`repro.kernels.tiered_gather`, or jnp).
  * **Misses** are batched per lookup: all absent shards touched by a batch
    are copied host->device in one stacked `device_put` + scatter (JAX
    dispatch is async, so the copy overlaps the caller's next ops).
    `prefetch()` runs the same fill from a *predicted* index set — the serve
    loop feeds it the previous decode step's accesses so fills overlap the
    dense compute of the next step.
  * **Eviction** is LRU over shards, with the current batch's shards pinned
    so a fill can never evict a shard the same gather still needs.  If a
    single batch touches more distinct shards than there are slots, the
    overflow rows are served straight from the host tier (counted in
    `stats["uncached"]`) — correctness never depends on cache capacity.
  * **Training write-back**: gradients w.r.t. values arrive as sparse
    (index, w*g) pairs from the custom VJP (`repro.memstore.interp`) and are
    applied as a sparse SGD step (`writeback_lr`) directly to the cached
    copy, marking the slot *dirty*; dirty slots are written back to their
    host shard on eviction, `flush()`, or checkpoint save.  This mirrors how
    production embedding tables own their sparse optimizer step instead of
    routing the table through the dense Adam.
  * **Quantized storage** (`TieredSpec.quant` of int8 | fp8): both tiers
    hold 1-byte payload rows plus per-row fp32 scales (`repro.quant`), so
    host capacity, the device-cache budget, and every host->device fill
    shrink ~4x.  Gathers dequantize on device (the interpolation stays
    fp32); the write-back dequantizes touched rows, applies the update,
    and requantizes with **stochastic rounding** so sub-quantum updates
    survive in expectation.

See docs/memstore.md for the full design narrative and docs/architecture.md
for where this store sits among the four lookup paths.
"""

from __future__ import annotations

import collections
import dataclasses
import os
import tempfile
import threading
import time
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs, quant
from repro.core import lookup
from repro.kernels import interpret_mode


@dataclasses.dataclass(frozen=True)
class TieredSpec:
    """Static configuration of a tiered table (hashable: rides LRAMConfig)."""

    shard_rows: int = 2048      # rows per shard (power of two)
    cache_slots: int = 32       # device-resident shards
    backing: str = "ram"        # ram | mmap
    backing_dir: str | None = None   # mmap only; default: a tempdir
    use_pallas: bool = False    # indirected-gather kernel vs jnp reference
    quant: str = "none"         # none | int8 | fp8: 1-byte rows + row scales

    def __post_init__(self):
        if self.shard_rows & (self.shard_rows - 1):
            raise ValueError("shard_rows must be a power of two")
        if self.cache_slots < 1:
            raise ValueError("need at least one cache slot")
        if self.backing not in ("ram", "mmap"):
            raise ValueError(f"unknown backing {self.backing!r}")
        if self.quant != "none":
            quant.check_kind(self.quant)


class TieredValueStore:
    """Host-offloaded (N, m) value table with a device-resident hot cache.

    Registered as a *leafless* pytree node, so it can sit at
    ``params["values"]`` and ride through jit/grad/optimizer tree maps
    untouched; `repro.checkpoint` detects it and streams shards to disk.
    """

    def __init__(self, num_rows: int, m: int, spec: TieredSpec,
                 *, dtype=np.float32):
        if num_rows % spec.shard_rows:
            raise ValueError(
                f"num_rows={num_rows} not divisible by "
                f"shard_rows={spec.shard_rows}"
            )
        self.spec = spec
        self.num_rows = num_rows
        self.m = m
        self.dtype = np.dtype(dtype)  # logical dtype (dequantized values)
        self.quant = spec.quant
        self.storage_dtype = (
            quant.storage_dtype(self.quant) if self.quant != "none"
            else self.dtype
        )
        self.shard_rows = spec.shard_rows
        self.num_shards = num_rows // spec.shard_rows
        self.cache_slots = min(spec.cache_slots, self.num_shards)
        self._log2R = self.shard_rows.bit_length() - 1

        self._host, self._host_scale = self._alloc_host()
        # device tier + indirection; quantized stores cache the 1-byte
        # payload + per-row scales, so the cache budget also shrinks ~4x
        self.cache_np = np.zeros(
            (self.cache_slots, self.shard_rows, m),
            self.storage_dtype if self.quant != "none" else np.float32,
        )
        self.cache_scale_np = (
            np.zeros((self.cache_slots, self.shard_rows), np.float32)
            if self.quant != "none" else None
        )
        self._cache_dev: jax.Array | None = None
        self._scale_dev: jax.Array | None = None
        # write-back requantization noise (stochastic rounding, int8)
        self._wb_rng = np.random.default_rng(0)
        self._shard_slot = np.full(self.num_shards, -1, np.int32)
        self._slot_shard = np.full(self.cache_slots, -1, np.int32)
        self._lru: collections.OrderedDict[int, int] = collections.OrderedDict()
        self._free = list(range(self.cache_slots - 1, -1, -1))
        self._dirty: set[int] = set()
        self._dev_stale: set[int] = set()

        # training write-back (sparse SGD; set by the trainer)
        self.writeback_lr = 0.0
        self.last_access: np.ndarray | None = None

        self._traced_interp = None  # built lazily by repro.memstore.interp
        # per-shard access counts (usage telemetry, repro.memctl): unlike
        # `stats`, indexed by shard so dead/hot regions are localizable
        self.shard_access = np.zeros(self.num_shards, np.int64)
        # guards cache residency + stat counters: fills run on the
        # prefetch worker pool (ShardedTieredStore fan-out) and lookup
        # callbacks run on XLA's io_callback threads, so every mutator of
        # `stats` / LRU / cache mirrors below takes this re-entrant lock.
        # Readers of individual stat values stay lock-free (a single dict
        # read is atomic); only read-modify-write needs the guard.
        self._lock = threading.RLock()
        self.reset_stats()

    # ------------------------------------------------------------------ init

    def _alloc_host(self) -> tuple[np.ndarray, np.ndarray | None]:
        shape = (self.num_shards, self.shard_rows, self.m)
        sshape = shape[:-1]
        vdtype = self.storage_dtype
        if self.spec.backing == "ram":
            values = np.zeros(shape, vdtype)
            scales = (np.zeros(sshape, np.float32)
                      if self.quant != "none" else None)
            return values, scales
        d = self.spec.backing_dir or tempfile.mkdtemp(prefix="memstore_")
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, f"values_{self.num_rows}x{self.m}.npy")
        values = np.lib.format.open_memmap(
            path, mode="w+", dtype=vdtype, shape=shape
        )
        scales = None
        if self.quant != "none":
            spath = os.path.join(d, f"scales_{self.num_rows}x{self.m}.npy")
            scales = np.lib.format.open_memmap(
                spath, mode="w+", dtype=np.float32, shape=sshape
            )
        return values, scales

    @classmethod
    def from_dense(cls, values: np.ndarray, spec: TieredSpec,
                   **kw) -> "TieredValueStore":
        values = np.asarray(values)
        n, m = values.shape
        dtype = values.dtype if spec.quant == "none" else np.float32
        store = cls(n, m, spec, dtype=dtype, **kw)
        store._fill_host(values)
        return store

    def _fill_host(self, values: np.ndarray) -> None:
        shaped = values.reshape(self.num_shards, self.shard_rows, self.m)
        if self.quant == "none":
            self._host[...] = shaped
        else:
            # nearest rounding here (init / load): identical to the dense
            # QuantizedTable built from the same draw
            q, s = quant.quantize_rows_np(shaped, self.quant)
            self._host[...] = q
            self._host_scale[...] = s

    def to_dense(self) -> np.ndarray:
        """Flush dirty slots and materialize the full (dequantized) table."""
        self.flush()
        if self.quant == "none":
            return np.array(self._host).reshape(self.num_rows, self.m)
        return quant.dequantize_rows_np(
            np.asarray(self._host), np.asarray(self._host_scale)
        ).reshape(self.num_rows, self.m)

    def load_dense(self, values: np.ndarray) -> None:
        """Replace table contents; invalidates the cache."""
        values = np.asarray(values)
        if values.shape != (self.num_rows, self.m):
            raise ValueError(
                f"shape {values.shape} != {(self.num_rows, self.m)}"
            )
        self._invalidate_cache()
        self._fill_host(values)

    def _invalidate_cache(self) -> None:
        with self._lock:
            self._shard_slot[:] = -1
            self._slot_shard[:] = -1
            self._lru.clear()
            self._free = list(range(self.cache_slots - 1, -1, -1))
            self._dirty.clear()
            self._dev_stale.clear()
            self._cache_dev = None
            self._scale_dev = None

    # ----------------------------------------------------------- addressing

    def _split(self, flat_idx: np.ndarray):
        flat_idx = flat_idx.astype(np.int64)
        return flat_idx >> self._log2R, flat_idx & (self.shard_rows - 1)

    # -------------------------------------------------- residency / mapping

    def _ensure_resident(self, shards: Iterable[int]) -> None:
        """Make `shards` cache-resident where capacity allows (LRU evict,
        current request pinned).  Fills update the host-side cache mirror
        and mark slots for the next batched device sync."""
        pinned = set(int(s) for s in shards)
        t0 = time.perf_counter()
        fills = evictions = 0
        with self._lock:
            for s in sorted(pinned):
                if self._shard_slot[s] >= 0:  # hit: touch
                    self._lru.move_to_end(s)
                    continue
                if self._free:
                    slot = self._free.pop()
                else:
                    victim = next(
                        (sh for sh in self._lru if sh not in pinned), None
                    )
                    if victim is None:  # whole cache pinned by this batch
                        continue
                    slot = self._lru.pop(victim)
                    self._writeback_slot(slot)
                    self._shard_slot[victim] = -1
                    self.stats["evictions"] += 1
                    evictions += 1
                self.cache_np[slot] = self._host[s]
                if self.quant != "none":
                    self.cache_scale_np[slot] = self._host_scale[s]
                self._shard_slot[s] = slot
                self._slot_shard[slot] = s
                self._lru[s] = slot
                self._lru.move_to_end(s)
                self._dev_stale.add(slot)
                self.stats["fills"] += 1
                fills += 1
        if fills:
            obs.counter("memstore.fills").inc(fills)
            obs.histogram("memstore.fill_s").observe(
                time.perf_counter() - t0
            )
        if evictions:
            obs.counter("memstore.evictions").inc(evictions)

    def _map(self, flat_idx: np.ndarray, *, count: bool = True,
             valid_elems: int | None = None):
        """(shard, row, slot, resident_mask) for flat global row ids,
        servicing misses along the way.  `valid_elems` limits the stat
        counting to the leading prefix — callers that pad a batch to a
        compile bucket (weight-0 duplicates) must not inflate
        hits/misses/uncached with phantom accesses."""
        shard, row = self._split(flat_idx)
        resident_before = self._shard_slot[shard] >= 0
        self._ensure_resident(np.unique(shard))
        slot = self._shard_slot[shard]
        mask = slot >= 0
        if count:
            v = slice(None) if valid_elems is None else slice(0, valid_elems)
            hits = int(resident_before[v].sum())
            misses = int((~resident_before[v] & mask[v]).sum())
            uncached = int((~mask[v]).sum())
            with self._lock:
                self.last_access = flat_idx  # feeds prefetch_last()
                self.stats["lookups"] += 1
                self.stats["hits"] += hits
                self.stats["misses"] += misses
                self.stats["uncached"] += uncached
                np.add.at(self.shard_access, shard[v], 1)
            obs.counter("memstore.hits").inc(hits)
            obs.counter("memstore.misses").inc(misses)
            obs.counter("memstore.uncached").inc(uncached)
        return shard, row, slot.astype(np.int64), mask

    def prefetch(self, idx, *, sync_device: bool = True) -> None:
        """Warm the cache for a predicted index set (e.g. the previous decode
        step's accesses) without touching hit/miss stats; the device copy is
        dispatched asynchronously and overlaps the caller's compute.
        `sync_device=False` fills only the host-side cache mirror — the
        right mode when the consumer is the traced (io_callback) lookup,
        which reads `cache_np`; the device mirror then syncs lazily on the
        next eager gather."""
        flat = np.asarray(idx).reshape(-1)
        shard, _ = self._split(flat)
        self._ensure_resident(np.unique(shard))
        if sync_device:
            self._sync_device()

    def prefetch_last(self, *, sync_device: bool = False) -> None:
        """Prefetch from the previous lookup's accesses — the serve loop's
        next-step predictor (decode locality).  Refreshes those shards to
        MRU and re-attempts fills for any that overflowed or were evicted,
        so the fill overlaps the next step's dense compute.  Defaults to
        host-mirror-only: the jitted decode path gathers via io_callback
        from `cache_np`, so an eager device upload here would be traffic
        nothing consumes."""
        if self.last_access is not None:
            self.prefetch(self.last_access, sync_device=sync_device)

    def warm(self, shards: Iterable[int] | None = None) -> None:
        """Fill the cache ahead of serving (default: lowest-id shards)."""
        if shards is None:
            shards = range(self.cache_slots)
        self._ensure_resident(shards)
        self._sync_device()

    # ------------------------------------------------------- device mirror

    def _sync_device(self) -> None:
        with self._lock:
            if self._cache_dev is None:
                self._cache_dev = jnp.asarray(self.cache_np)
                synced = self.cache_np.nbytes
                if self.quant != "none":
                    self._scale_dev = jnp.asarray(self.cache_scale_np)
                    synced += self.cache_scale_np.nbytes
                self._dev_stale.clear()
                self.stats["fill_bytes"] += synced
            elif not self._dev_stale:
                return
            else:
                slots = np.fromiter(sorted(self._dev_stale), np.int32)
                # one stacked host->device copy
                block = jnp.asarray(self.cache_np[slots])
                self._cache_dev = self._cache_dev.at[
                    jnp.asarray(slots)
                ].set(block)
                synced = self.cache_np[slots].nbytes
                if self.quant != "none":
                    sblock = jnp.asarray(self.cache_scale_np[slots])
                    self._scale_dev = self._scale_dev.at[
                        jnp.asarray(slots)
                    ].set(sblock)
                    synced += self.cache_scale_np[slots].nbytes
                self._dev_stale.clear()
                self.stats["fill_bytes"] += synced
        obs.counter("memstore.fill_bytes").inc(synced)

    @property
    def cache_dev(self) -> jax.Array:
        self._sync_device()
        return self._cache_dev

    @property
    def cache_scale_dev(self) -> jax.Array:
        self._sync_device()
        return self._scale_dev

    # ------------------------------------------------------------- lookups

    def gather(self, idx, w, *, valid_elems: int | None = None) -> jax.Array:
        """sum_k w[..., k] * values[idx[..., k]] -> (..., m), gathering from
        the device-resident cache (misses are filled first; rows of shards
        that cannot fit are appended from the host tier).  `valid_elems`:
        see `_map` — stat counting for bucket-padded batches."""
        idx_np = np.asarray(idx)
        lead, top_k = idx_np.shape[:-1], idx_np.shape[-1]
        flat = idx_np.reshape(-1)
        shard, row, slot, mask = self._map(flat, valid_elems=valid_elems)
        slot_rows = np.where(mask, slot * self.shard_rows + row, 0)
        quantized = self.quant != "none"
        cache_flat = self.cache_dev.reshape(-1, self.m)
        scale_flat = (self.cache_scale_dev.reshape(-1) if quantized
                      else None)
        table, scales = cache_flat, scale_flat
        if not mask.all():
            inv = ~mask
            ovf = self._host[shard[inv], row[inv]]
            slot_rows[inv] = cache_flat.shape[0] + np.arange(len(ovf))
            # pad the overflow block to a power-of-two bucket: the jitted
            # gather then sees O(log batch) distinct table shapes, not one
            # fresh XLA compile per distinct uncached-row count
            pad = 1 << max(0, (len(ovf) - 1)).bit_length()
            block = np.zeros((pad, self.m), self.cache_np.dtype)
            block[:len(ovf)] = ovf
            table = jnp.concatenate([cache_flat, jnp.asarray(block)], axis=0)
            if quantized:  # overflow rows stay 1-byte: scales ride along
                sblock = np.zeros((pad,), np.float32)
                sblock[:len(ovf)] = self._host_scale[shard[inv], row[inv]]
                scales = jnp.concatenate(
                    [scale_flat, jnp.asarray(sblock)], axis=0
                )
        w_flat = jnp.asarray(w).reshape(-1, top_k).astype(jnp.float32)
        sr = jnp.asarray(slot_rows.reshape(-1, top_k).astype(np.int32))
        if self.spec.use_pallas and mask.all():
            interpret = interpret_mode()
            idx_dev = jnp.asarray(flat.reshape(-1, top_k).astype(np.int32))
            slot_dev = jnp.asarray(self._shard_slot)
            if quantized:
                kernel = lookup.kernel_gather("pallas", "tiered-quant")
                out = kernel(
                    cache_flat, scale_flat, idx_dev, slot_dev, w_flat,
                    shard_rows=self.shard_rows, interpret=interpret,
                )
            else:
                kernel = lookup.kernel_gather("pallas", "tiered")
                out = kernel(
                    cache_flat, idx_dev, slot_dev, w_flat,
                    shard_rows=self.shard_rows, interpret=interpret,
                )
        elif quantized:
            out = _gather_rows_device_quant(table, scales, sr, w_flat)
        else:
            out = _gather_rows_device(table, sr, w_flat)
        return out.reshape(*lead, self.m)

    def gather_rows_host(self, idx) -> np.ndarray:
        """values[idx] -> (idx.shape + (m,)) float32, via the same cache
        machinery but reading the host-side cache mirror.  This is the
        io_callback body used when the lookup runs inside jit/grad."""
        idx_np = np.asarray(idx)
        flat = idx_np.reshape(-1)
        shard, row, slot, mask = self._map(flat)
        rows = np.empty((flat.size, self.m), np.float32)
        if self.quant != "none":
            scales = np.empty((flat.size,), np.float32)
            if mask.any():
                rows[mask] = self.cache_np[slot[mask], row[mask]]
                scales[mask] = self.cache_scale_np[slot[mask], row[mask]]
            if not mask.all():
                inv = ~mask
                rows[inv] = self._host[shard[inv], row[inv]]
                scales[inv] = self._host_scale[shard[inv], row[inv]]
            rows *= scales[:, None]  # dequant: callback contract is fp32
        else:
            if mask.any():
                rows[mask] = self.cache_np[slot[mask], row[mask]]
            if not mask.all():
                inv = ~mask
                rows[inv] = self._host[shard[inv], row[inv]]
        return rows.reshape(*idx_np.shape, self.m)

    # ------------------------------------------------------------ training

    def apply_writeback(self, idx, wg) -> None:
        """Sparse SGD write-back: values[idx] -= writeback_lr * wg.

        `wg` is w ⊗ dL/dout from the custom VJP (dL/dvalues restricted to
        the touched rows).  Cached rows are updated in the cache (slot goes
        dirty); rows of non-resident shards update the host tier directly."""
        if self.writeback_lr <= 0.0:
            return
        idx_np = np.asarray(idx)
        flat = idx_np.reshape(-1)
        upd = -self.writeback_lr * np.asarray(wg, np.float32).reshape(
            -1, self.m
        )
        with self._lock:
            if self.quant != "none":
                self._apply_writeback_quant(flat, upd)
                self.stats["writebacks"] += 1
            else:
                shard, row = self._split(flat)
                slot = self._shard_slot[shard].astype(np.int64)
                mask = slot >= 0
                if mask.any():
                    np.add.at(
                        self.cache_np, (slot[mask], row[mask]), upd[mask]
                    )
                    touched = set(np.unique(slot[mask]).tolist())
                    self._dirty |= touched
                    self._dev_stale |= touched
                if not mask.all():
                    inv = ~mask
                    np.add.at(
                        self._host, (shard[inv], row[inv]),
                        upd[inv].astype(self._host.dtype),
                    )
                self.stats["writebacks"] += 1
        obs.counter("memstore.writebacks").inc()

    def _apply_writeback_quant(self, flat: np.ndarray,
                               upd: np.ndarray) -> None:
        """Quantization-aware sparse step: dequantize each touched row,
        apply the accumulated update, requantize with a fresh per-row scale
        and **stochastic rounding** (int8; `repro.quant`) so updates smaller
        than one quantization step survive in expectation — the same
        error-containment idea as the int8 gradient codec in
        `repro.optim.compression`, applied at the storage boundary."""
        uniq, inv = np.unique(flat, return_inverse=True)
        acc = np.zeros((len(uniq), self.m), np.float32)
        np.add.at(acc, inv, upd)  # duplicate indices accumulate first
        shard, row = self._split(uniq)
        slot = self._shard_slot[shard].astype(np.int64)
        mask = slot >= 0
        rng = self._wb_rng if self.quant == "int8" else None
        if mask.any():
            sl, rw = slot[mask], row[mask]
            cur = quant.dequantize_rows_np(
                self.cache_np[sl, rw], self.cache_scale_np[sl, rw]
            )
            q, s = quant.quantize_rows_np(
                cur + acc[mask], self.quant, rng=rng
            )
            self.cache_np[sl, rw] = q
            self.cache_scale_np[sl, rw] = s
            touched = set(np.unique(sl).tolist())
            self._dirty |= touched
            self._dev_stale |= touched
        if not mask.all():
            nm = ~mask
            sh, rw = shard[nm], row[nm]
            cur = quant.dequantize_rows_np(
                self._host[sh, rw], self._host_scale[sh, rw]
            )
            q, s = quant.quantize_rows_np(
                cur + acc[nm], self.quant, rng=rng
            )
            self._host[sh, rw] = q
            self._host_scale[sh, rw] = s

    def _flush_slot_to_host(self, slot: int) -> None:
        shard = self._slot_shard[slot]
        if self.quant != "none":
            self._host[shard] = self.cache_np[slot]
            self._host_scale[shard] = self.cache_scale_np[slot]
        else:
            self._host[shard] = self.cache_np[slot].astype(self.dtype)

    def _writeback_slot(self, slot: int) -> None:
        if slot in self._dirty:
            self._flush_slot_to_host(slot)
            self._dirty.discard(slot)
            self.stats["dirty_writebacks"] += 1

    def flush(self) -> None:
        """Write every dirty cached shard back to its host shard."""
        with self._lock:
            for slot in sorted(self._dirty):
                self._flush_slot_to_host(slot)
                self.stats["dirty_writebacks"] += 1
            self._dirty.clear()

    # ---------------------------------------------------------- checkpoint

    def shard_host(self, i: int) -> np.ndarray:
        """Shard `i`'s stored payload as seen through the cache (dirty slots
        win).  Quantized stores return the 1-byte payload; its scales come
        from `shard_scale_host`."""
        slot = int(self._shard_slot[i])
        if slot >= 0 and slot in self._dirty:
            if self.quant != "none":
                return np.asarray(self.cache_np[slot])
            return self.cache_np[slot].astype(self.dtype)
        return np.asarray(self._host[i])

    def shard_scale_host(self, i: int) -> np.ndarray:
        """Per-row fp32 scales of shard `i` (quantized stores only)."""
        assert self.quant != "none"
        slot = int(self._shard_slot[i])
        if slot >= 0 and slot in self._dirty:
            return np.asarray(self.cache_scale_np[slot])
        return np.asarray(self._host_scale[i])

    def load_shard(self, i: int, arr: np.ndarray,
                   scale: np.ndarray | None = None) -> None:
        """Replace shard `i`.  `arr` may be fp values (requantized on the
        way in if this store is quantized) or a 1-byte payload with its
        per-row `scale` (dequantized if this store is dense) — this is what
        makes quantized<->dense checkpoint restore work shard by shard."""
        if arr.shape != (self.shard_rows, self.m):
            raise ValueError(
                f"shard {i}: shape {arr.shape} != "
                f"{(self.shard_rows, self.m)}"
            )
        if scale is not None and arr.dtype.itemsize != 1:
            raise ValueError("scale given but payload is not quantized")
        if self.quant != "none":
            if scale is None:  # fp input: quantize (nearest) on the way in
                q, s = quant.quantize_rows_np(
                    np.asarray(arr, np.float32), self.quant
                )
            elif arr.dtype != self.storage_dtype:  # cross-kind: requantize
                q, s = quant.quantize_rows_np(
                    quant.dequantize_rows_np(arr, scale), self.quant
                )
            else:
                q, s = arr, np.asarray(scale, np.float32)
            self._host[i] = q
            self._host_scale[i] = s
            slot = int(self._shard_slot[i])
            if slot >= 0:  # refresh the cached copy too
                self.cache_np[slot] = q
                self.cache_scale_np[slot] = s
                self._dirty.discard(slot)
                self._dev_stale.add(slot)
            return
        if scale is not None:  # quantized checkpoint into a dense store
            arr = quant.dequantize_rows_np(arr, scale)
        self._host[i] = arr.astype(self.dtype)
        slot = int(self._shard_slot[i])
        if slot >= 0:  # refresh the cached copy too
            self.cache_np[slot] = arr.astype(np.float32)
            self._dirty.discard(slot)
            self._dev_stale.add(slot)

    # ------------------------------------------------------------- lifecycle

    def _read_rows_raw(self, rows: np.ndarray):
        """(payload, scales|None) for global row ids, in *storage* form —
        1-byte payload + per-row scales for quantized stores, fp values
        otherwise.  Reads the host tier (dirty cache slots flushed first),
        without touching cache residency, LRU order, or stats: this is the
        bulk-copy path growth and migration use, not a lookup."""
        self.flush()
        shard, row = self._split(np.asarray(rows).reshape(-1))
        payload = np.asarray(self._host[shard, row])
        scales = (np.asarray(self._host_scale[shard, row])
                  if self.quant != "none" else None)
        return payload, scales

    def grow_rows(self, new_num_rows: int, parents: np.ndarray) -> None:
        """Append rows [num_rows, new_num_rows), each initialised from its
        (old-table) parent row id in `parents` — in place.

        Growth is append-only by construction (`repro.core.indexing.
        grow_torus` preserves every old flat index), so the existing host
        shards keep their ids and the device cache — slots, shard→slot
        indirection, LRU order, dirty flags — stays valid untouched: the
        pause is one host-side copy, no device traffic.  Quantized stores
        copy parent payload + per-row scale verbatim, so pre-growth
        lookups reproduce bit-exactly for every storage kind.  The cache
        slot count is left as built (`TieredSpec.cache_slots` already caps
        it); appended shards simply compete for the same slots.
        """
        delta = new_num_rows - self.num_rows
        if delta <= 0 or delta % self.shard_rows:
            raise ValueError(
                f"new_num_rows={new_num_rows} must exceed {self.num_rows} "
                f"by a multiple of shard_rows={self.shard_rows}"
            )
        parents = np.asarray(parents, np.int64).reshape(-1)
        if parents.size != delta:
            raise ValueError(
                f"need {delta} parent rows, got {parents.size}"
            )
        if parents.size and (parents.min() < 0
                             or parents.max() >= self.num_rows):
            raise ValueError("parent row ids must index the old table")
        with self._lock:
            payload, scales = self._read_rows_raw(parents)
            new_shards = delta // self.shard_rows
            pay3 = payload.reshape(new_shards, self.shard_rows, self.m)
            sc2 = (scales.reshape(new_shards, self.shard_rows)
                   if scales is not None else None)
            old_host, old_scale = self._host, self._host_scale
            old_n_shards = self.num_shards
            self.num_rows = new_num_rows
            self.num_shards += new_shards
            if self.spec.backing == "ram":
                self._host = np.concatenate([old_host, pay3])
                if self.quant != "none":
                    self._host_scale = np.concatenate([old_scale, sc2])
            else:  # mmap: a fresh file at the new shape (name encodes rows)
                self._host, self._host_scale = self._alloc_host()
                self._host[:old_n_shards] = old_host
                self._host[old_n_shards:] = pay3
                if self.quant != "none":
                    self._host_scale[:old_n_shards] = old_scale
                    self._host_scale[old_n_shards:] = sc2
            self._shard_slot = np.concatenate([
                self._shard_slot, np.full(new_shards, -1, np.int32)
            ])
            self.shard_access = np.concatenate([
                self.shard_access, np.zeros(new_shards, np.int64)
            ])
            self.last_access = None  # old ids stay valid, but re-prime

    def row_stats(self) -> tuple[np.ndarray, int]:
        """(per-shard access counts, rows per shard) — the store-side input
        to `repro.memctl.telemetry` (coarse: one bin per host shard)."""
        return self.shard_access.copy(), self.shard_rows

    # --------------------------------------------------------------- stats

    def reset_stats(self) -> None:
        with self._lock:
            self.shard_access[:] = 0
            self.stats = {
                "lookups": 0, "hits": 0, "misses": 0, "uncached": 0,
                "fills": 0, "evictions": 0, "writebacks": 0,
                "dirty_writebacks": 0, "fill_bytes": 0,
            }

    def bytes_per_entry(self) -> int:
        """Host-tier storage bytes per table row (payload + scale)."""
        if self.quant == "none":
            return self.m * self.dtype.itemsize
        return quant.bytes_per_entry(self.m, self.quant)

    def hit_rate(self) -> float:
        total = self.stats["hits"] + self.stats["misses"] \
            + self.stats["uncached"]
        return self.stats["hits"] / total if total else 0.0

    def resident_shards(self) -> list[int]:
        """Shards currently cached, least- to most-recently used."""
        return list(self._lru)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (
            f"TieredValueStore(rows={self.num_rows}, m={self.m}, "
            f"shards={self.num_shards}x{self.shard_rows}, "
            f"slots={self.cache_slots}, backing={self.spec.backing!r}, "
            f"quant={self.quant!r}, hit_rate={self.hit_rate():.3f})"
        )


@jax.jit
def _gather_rows_device(table, slot_rows, w):
    """rows = table[slot_rows]; out = einsum('nk,nkm->nm', w, rows)."""
    rows = jnp.take(table, slot_rows, axis=0)
    return jnp.einsum("nk,nkm->nm", w, rows)


@jax.jit
def _gather_rows_device_quant(table_q, table_scale, slot_rows, w):
    """Quantized twin: rows are gathered in 1-byte form, dequantized by the
    gathered per-row scales, and interpolated in fp32 — folding the scale
    into the weights so no (n, k, m) fp32 row tensor is materialized."""
    rows = jnp.take(table_q, slot_rows, axis=0)  # (n, k, m) int8/fp8
    ws = w * jnp.take(table_scale, slot_rows, axis=0)
    return jnp.einsum("nk,nkm->nm", ws, rows.astype(jnp.float32))


# Leafless pytree node: tree maps (grad, optimizer, sharding, jit flattening)
# pass the store through by aux-data identity without ever touching it.
jax.tree_util.register_pytree_node(
    TieredValueStore,
    lambda s: ((), s),
    lambda aux, children: aux,
)
lookup.register_store_type(TieredValueStore)


def find_stores(tree) -> list[tuple[str, TieredValueStore]]:
    """(path, store) for every distinct offloaded store in a pytree.

    Thin delegation to `repro.core.lookup.find_stores`, which walks the
    registered store types (TieredValueStore here, ShardedTieredStore in
    repro.distributed.sharded_lram)."""
    return lookup.find_stores(tree)
