"""Differentiable jit'd wrappers around the Pallas kernels.

The paper's CUDA kernel emits (indices, weights, dweights/dquery) and a
PyTorch autograd wrapper consumes them.  Here the same contract is a
jax.custom_vjp pair:

  * forward: Pallas kernels (or the jnp reference when `use_pallas=False`)
  * backward:
      - d values = scatter-add of w (x) g over the touched rows (sparse:
        <= top_k rows per query),
      - d query via the analytic kernel derivative
        dw/dq = -(1 - d^2/8)^3 * (q - k)   (f(r)=max(0,1-r^2/8)^4),
        with the neighbor position k recovered from the stored index by
        nearest-image unwrapping on the torus.

`interpret=True` runs the Pallas path in interpret mode (the CPU
backend); with `interpret=False` the same code compiles to Mosaic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import indexing, lattice
from repro.kernels import e8_lookup, gather_interp, ref


def _decode_index_table(spec: indexing.TorusSpec) -> None:
    """Torus points for every index — only used for small test tables."""
    return jnp.asarray(
        indexing.decode_index(np.arange(spec.num_locations), spec)
    )


def _nearest_image_delta(q: jax.Array, k_wrapped: jax.Array, K) -> jax.Array:
    """q - k for the nearest torus image of k (exact within kernel radius)."""
    Kv = jnp.asarray(K, dtype=q.dtype)
    delta = q - k_wrapped
    return delta - Kv * jnp.round(delta / Kv)


# ---------------------------------------------------------------------------
# lookup = query + gather, fused behind one custom_vjp
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def lram_lookup(
    values: jax.Array,
    q: jax.Array,
    spec: indexing.TorusSpec,
    top_k: int = lattice.DEFAULT_TOP_K,
    use_pallas: bool = True,
    interpret: bool = True,
):
    """out[t] = sum_k f(d(q_t, k)) * values[k] over the top_k nearest slots."""
    out, _ = _lookup_fwd(values, q, spec, top_k, use_pallas, interpret)
    return out


def _lookup_fwd(values, q, spec, top_k, use_pallas, interpret):
    if use_pallas:
        idx, w = e8_lookup.lram_query_pallas(
            q, spec, top_k, interpret=interpret
        )
        out = gather_interp.gather_interp_pallas(
            values, idx, w, interpret=interpret
        )
    else:
        idx, w = ref.lram_query_ref(q, spec, top_k)
        out = ref.gather_interp_ref(values, idx, w)
    return out.astype(jnp.float32), (values, q, idx, w)


def _lookup_bwd(spec, top_k, use_pallas, interpret, res, g):
    values, q, idx, w = res
    g = g.astype(jnp.float32)
    # ---- d values: sparse scatter-add (the paper's backward CUDA kernel) --
    m = values.shape[-1]
    flat_idx = idx.reshape(-1)
    flat_wg = (w[..., None] * g[..., None, :]).reshape(-1, m)
    dvalues = jnp.zeros(values.shape, jnp.float32).at[flat_idx].add(flat_wg)
    # ---- d query via analytic dw/dq --------------------------------------
    # recover neighbor positions from indices (nearest torus image)
    pts = _points_from_indices(idx, spec)  # (..., k, 8)
    delta = _nearest_image_delta(q[..., None, :], pts, spec.K)  # (...,k,8)
    d2 = jnp.sum(delta * delta, axis=-1)
    relu = jnp.maximum(0.0, 1.0 - d2 / lattice.RADIUS_SQ)
    # dw/dq = -(relu)^3 * delta ; dL/dw_k = g . values[idx_k]
    rows = jnp.take(values, idx, axis=0).astype(jnp.float32)
    dL_dw = jnp.einsum("...m,...km->...k", g, rows)
    dq = jnp.sum(
        (dL_dw * (relu**3))[..., None] * (-delta), axis=-2
    )
    return dvalues.astype(values.dtype), dq.astype(q.dtype)


def _points_from_indices(idx: jax.Array, spec: indexing.TorusSpec):
    """Invert the index bijection inside the graph (vectorised int ops)."""
    M = spec.M
    p = idx & 1
    r = idx >> 1
    half = M[7] >> 1
    j8 = jnp.mod(r, half)
    idx7 = r // half
    us = []
    for i in reversed(range(7)):
        us.append(jnp.mod(idx7, M[i]))
        idx7 = idx7 // M[i]
    u = jnp.stack(us[::-1], axis=-1)  # (..., 7)
    qpar = jnp.sum(u, axis=-1) & 1
    u8 = 2 * j8 + qpar
    full = jnp.concatenate([u, u8[..., None]], axis=-1)
    return (2 * full + p[..., None]).astype(jnp.float32)


lram_lookup.defvjp(_lookup_fwd, _lookup_bwd)


def make_interp_impl(spec: indexing.TorusSpec, top_k: int,
                     *, use_pallas: bool = True, interpret: bool = True):
    """A legacy callable `interp_impl` hook for repro.core.lram.lram_apply.

    Deprecated: the plan registry (`repro.core.lookup`) resolves
    `interp_impl="pallas"` to the same kernels with the sparse-backward
    custom VJP attached; passing this hook goes through the callable
    deprecation shim.  Kept for direct use outside lram_apply.

    Note: when plugged into lram_apply the query pipeline still runs in jnp
    (lram_apply computes idx/w itself); this hook swaps only the gather.
    Use `lram_lookup` directly for the fully-fused differentiable path.
    """

    def interp(values, idx, w):
        if use_pallas:
            return gather_interp.gather_interp_pallas(
                values, idx, w, interpret=interpret
            )
        return ref.gather_interp_ref(values, idx, w)

    return interp
