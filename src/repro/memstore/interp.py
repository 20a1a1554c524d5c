"""The tiered placement backend: differentiable host-offloaded lookup.

Registers the `"tiered"` placement with the lookup-plan registry
(`repro.core.lookup`), so `interp_impl="tiered"` resolves to a plan whose
table is a `TieredValueStore` and whose interp is `tiered_interp` below.
The same entry point also drives the sharded-tiered placement
(`repro.distributed.sharded_lram.ShardedTieredStore` routes the per-range
cache walks behind the identical `gather` / `gather_rows_host` /
`apply_writeback` surface).

Two execution modes behind one entry point, `tiered_interp(store, idx, w)`:

  * **eager** (serve prefill, benchmarks, tests): concrete index arrays —
    cache fills are real stacked host->device copies and the gather runs on
    the device-resident cache (`TieredValueStore.gather`).
  * **traced** (jitted train step / decode step): the index array is a
    tracer, so the cache walk happens in `jax.experimental.io_callback`
    bodies.  Forward gathers the touched rows through the store (ordered —
    cache state mutates); backward emits the analytic dL/dw on device and
    hands the sparse dL/dvalues (w ⊗ g per touched row) to the store's
    write-back, which applies the sparse SGD step and marks shards dirty.

The custom VJP mirrors `repro.kernels.ops.lram_lookup`'s backward contract:
d values is the paper's sparse scatter-add (here: host-side into tiered
shards), d w is the gathered-row dot.  Query gradients keep flowing through
`w` exactly as in the dense reference path, so swapping a model between
dense and tiered changes *where the table lives*, not its gradients.

Quantized stores (`TieredSpec.quant`) need no special casing here: the
forward callback (`gather_rows_host`) hands back already-dequantized fp32
rows, and the backward's (index, w (x) g) pairs feed `apply_writeback`,
which requantizes dirty rows with stochastic rounding (see
repro.memstore.store / repro.quant).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import io_callback

from repro import obs
from repro.core import lookup
from repro.memstore.store import TieredValueStore


# On few-core CPU hosts, force synchronous dispatch.  io_callback bodies run
# on the CPU client's executor threads; with async dispatch, materialising the
# callback's own operands (np.asarray(idx)) waits on a device_put that needs
# the very thread the callback occupies — a hard deadlock when the pool has no
# spare thread (reproduced on 1-cpu hosts: jit(grad) of tiered_interp never
# returns).  The flag is latched when the CPU client is built, so it must be
# set at import time — before the first jax computation — and it only affects
# the cpu backend, so setting it under an accelerator is harmless.
if (os.cpu_count() or 1) <= 2:
    jax.config.update("jax_cpu_enable_async_dispatch", False)


def tiered_interp(store, idx: jax.Array, w: jax.Array) -> jax.Array:
    """sum_k w[..., k] * store[idx[..., k]] -> (..., m); differentiable.

    `store` is a TieredValueStore or any object with the same
    gather / gather_rows_host / apply_writeback surface (the
    sharded-tiered range store)."""
    if isinstance(idx, jax.core.Tracer) or isinstance(w, jax.core.Tracer):
        if store._traced_interp is None:
            store._traced_interp = _build_traced_interp(store)
        return store._traced_interp(idx, w)
    return store.gather(idx, w)


def _build_traced_interp(store):
    m = store.m

    # host time per lookup: one span per callback, with its row count
    def _rows_cb(idx):
        idx = np.asarray(idx)
        with obs.span("memstore.gather_rows", rows=idx.size):
            return store.gather_rows_host(idx)

    def _writeback_cb(idx, wg):
        idx = np.asarray(idx)
        with obs.span("memstore.writeback", rows=idx.size):
            store.apply_writeback(idx, np.asarray(wg))
        return np.int32(0)

    def _rows(idx):
        shape = jax.ShapeDtypeStruct(tuple(idx.shape) + (m,), jnp.float32)
        # ordered: the callback mutates cache state (LRU, fills, stats)
        return io_callback(_rows_cb, shape, idx, ordered=True)

    @jax.custom_vjp
    def interp(idx, w):
        rows = _rows(idx)
        return jnp.einsum("...k,...km->...m", w.astype(jnp.float32), rows)

    def _fwd(idx, w):
        rows = _rows(idx)
        out = jnp.einsum("...k,...km->...m", w.astype(jnp.float32), rows)
        return out, (idx, w, rows)

    def _bwd(res, g):
        idx, w, rows = res
        g = g.astype(jnp.float32)
        dw = jnp.einsum("...m,...km->...k", g, rows)
        wg = w.astype(jnp.float32)[..., None] * g[..., None, :]
        token = io_callback(
            _writeback_cb, jax.ShapeDtypeStruct((), jnp.int32),
            idx, wg, ordered=True,
        )
        # tie the write-back effect into the returned cotangent
        dw = dw + jnp.zeros((), dw.dtype) * token.astype(dw.dtype)
        return (
            np.zeros(idx.shape, dtype=jax.dtypes.float0),
            dw.astype(w.dtype),
        )

    interp.defvjp(_fwd, _bwd)
    return interp


# ---------------------------------------------------------------------------
# the "tiered" placement backend (repro.core.lookup)
# ---------------------------------------------------------------------------

def _tiered_factory(cfg, storage: str, kernel: str) -> lookup.LookupPlan:
    spec = lookup.merged_tiered_spec(cfg, storage, kernel)
    if cfg.num_locations % spec.shard_rows:
        raise lookup.LookupPlanError(
            "tiered", storage, kernel,
            f"num_locations={cfg.num_locations} not divisible by "
            f"TieredSpec.shard_rows={spec.shard_rows}",
        )

    def build_table(dense):
        return TieredValueStore.from_dense(np.asarray(dense), spec)

    def interp(values, idx, w):
        if not isinstance(values, TieredValueStore):
            raise lookup.LookupPlanError(
                "tiered", storage, kernel,
                "params['values'] must be a TieredValueStore — init the "
                "layer with LRAMConfig(interp_impl='tiered')",
            )
        return tiered_interp(values, idx, w)

    return lookup.LookupPlan(
        placement="tiered", storage=storage, kernel=kernel,
        build_table=build_table, interp=interp,
        supports_prefetch=True, table_update="writeback",
        checkpoint_layout="shards",
        supports_growth=True, row_stats=True,
        build_empty=lambda: TieredValueStore(
            cfg.num_locations, cfg.m, spec
        ),
        supports_overlay=True,
    )


lookup.register_placement("tiered", _tiered_factory)
