"""Indirected value gather through the hot-shard cache (Pallas TPU).

Companion to `repro.kernels.gather_interp`: same bandwidth-critical
weighted gather, but the table operand is the *device cache* of a
`repro.memstore.TieredValueStore` — (cache_slots * shard_rows, m) — and the
global row id is translated through the shard->slot indirection table:

    cache_row(r) = slot_table[r >> log2(shard_rows)] * shard_rows
                   + (r & (shard_rows - 1))

The translation is a shift/mask and a gather from a table of `num_shards`
entries, done in XLA before the kernel; the kernel is the dense gather's
DMA kernel run on the cache.

All indices must be cache-resident (slot_table[shard] >= 0) — the store
guarantees this by pinning the current batch's shards and serving overflow
rows host-side before choosing this kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import lookup
from repro.kernels.gather_interp import (
    gather_interp_pallas,
    gather_interp_quant_pallas,
)


def _cache_rows(idx, slot_table, shard_rows: int) -> jax.Array:
    if shard_rows & (shard_rows - 1):
        raise ValueError("shard_rows must be a power of two")
    log2r = shard_rows.bit_length() - 1
    idx = idx.astype(jnp.int32)
    slot = jnp.take(slot_table.astype(jnp.int32), idx >> log2r, axis=0)
    return slot * shard_rows + (idx & (shard_rows - 1))


def tiered_gather_pallas(
    cache_flat: jax.Array,
    idx: jax.Array,
    slot_table: jax.Array,
    w: jax.Array,
    *,
    shard_rows: int,
    interpret: bool = False,
) -> jax.Array:
    """sum_k w[..., k] * cache_flat[indirect(idx[..., k])] -> (..., m).

    Args:
      cache_flat: (cache_slots * shard_rows, m) device cache, flattened.
      idx: (..., top_k) int32 *global* row ids (all cache-resident).
      slot_table: (num_shards,) int32 shard -> slot indirection (-1 absent).
      w: (..., top_k) interpolation weights.
      shard_rows: rows per shard (power of two; fixes the shift/mask).
    """
    return gather_interp_pallas(
        cache_flat, _cache_rows(idx, slot_table, shard_rows), w,
        interpret=interpret, name="lram_tiered_gather",
    )


def tiered_gather_quant_pallas(
    cache_flat: jax.Array,
    scale_flat: jax.Array,
    idx: jax.Array,
    slot_table: jax.Array,
    w: jax.Array,
    *,
    shard_rows: int,
    interpret: bool = False,
) -> jax.Array:
    """Quantized twin of `tiered_gather_pallas`: the device cache holds
    int8/fp8 payload rows plus per-row fp32 scales; both are gathered
    through the same shard->slot indirection and dequantized in VMEM.

    Args:
      cache_flat: (cache_slots * shard_rows, m) quantized device cache.
      scale_flat: (cache_slots * shard_rows,) fp32 per-row scales.
      idx / slot_table / w / shard_rows: as in `tiered_gather_pallas`.
    """
    return gather_interp_quant_pallas(
        cache_flat, scale_flat, _cache_rows(idx, slot_table, shard_rows), w,
        interpret=interpret, name="lram_tiered_gather_quant",
    )


def tiered_gather_ref(
    cache_flat: jax.Array,
    idx: jax.Array,
    slot_table: jax.Array,
    w: jax.Array,
    *,
    shard_rows: int,
) -> jax.Array:
    """jnp reference for the indirected gather (tests / CPU fallback)."""
    log2r = shard_rows.bit_length() - 1
    slot = jnp.take(slot_table, idx >> log2r, axis=0)
    rows = jnp.take(
        cache_flat, slot * shard_rows + (idx & (shard_rows - 1)), axis=0
    )
    return jnp.einsum("...k,...km->...m", w.astype(jnp.float32), rows)


def tiered_gather_quant_ref(
    cache_flat: jax.Array,
    scale_flat: jax.Array,
    idx: jax.Array,
    slot_table: jax.Array,
    w: jax.Array,
    *,
    shard_rows: int,
) -> jax.Array:
    """jnp reference for the quantized indirected gather."""
    log2r = shard_rows.bit_length() - 1
    slot = jnp.take(slot_table, idx >> log2r, axis=0)
    cache_rows = slot * shard_rows + (idx & (shard_rows - 1))
    rows = jnp.take(cache_flat, cache_rows, axis=0).astype(jnp.float32)
    ws = w.astype(jnp.float32) * jnp.take(scale_flat, cache_rows, axis=0)
    return jnp.einsum("...k,...km->...m", ws, rows)


# the indirected cells of the lookup-plan kernel registry: the tiered
# store's device-cache gather resolves these instead of importing this
# module by name (repro.core.lookup / repro.memstore.store)
lookup.register_kernel("pallas", "tiered", tiered_gather_pallas)
lookup.register_kernel("pallas", "tiered-quant", tiered_gather_quant_pallas)
