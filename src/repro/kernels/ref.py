"""Pure-jnp oracles for the Pallas kernels.

These are *the* reference semantics: every kernel test sweeps shapes/dtypes
and asserts allclose against these functions, which are themselves built on
the exhaustively-tested repro.core.lattice pipeline.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import indexing, lattice, lookup, lram


def lram_query_ref(
    q: jax.Array, spec: indexing.TorusSpec, top_k: int = lattice.DEFAULT_TOP_K
) -> tuple[jax.Array, jax.Array]:
    """Top-k (index, weight) pairs — same contract as lram_query_pallas."""
    return lram.indices_and_weights(q.astype(jnp.float32), spec, top_k)


def gather_interp_ref(
    values: jax.Array, idx: jax.Array, w: jax.Array
) -> jax.Array:
    """sum_k w_k * values[idx_k] — same contract as gather_interp_pallas."""
    return lram.gather_interp(values, idx, w.astype(jnp.float32))


def lookup_ref(
    values: jax.Array,
    q: jax.Array,
    spec: indexing.TorusSpec,
    top_k: int = lattice.DEFAULT_TOP_K,
) -> jax.Array:
    idx, w = lram_query_ref(q, spec, top_k)
    return gather_interp_ref(values, idx, w)


def _gather_interp_quant_ref(table, idx, w):
    from repro import quant

    return quant.gather_interp_quant(table, idx, w)


# the "reference" kernel axis of the lookup-plan registry: the plain jnp
# gather for QuantizedTables (repro.core.lookup); the fp32 one is this
# gather with the table gradient's kernel as its backward
# (`repro.kernels.scatter_add.gather_rows`)
lookup.register_kernel("reference", "quant", _gather_interp_quant_ref)
