"""Fused LRAM query kernel (Pallas TPU).

One kernel performs, per query tile, the paper's whole CUDA §2.6 pipeline:

  1. E8 nearest-point decode (both D8 cosets, branch-free),
  2. canonicalization into the fundamental region F via a 19-comparator
     Batcher sorting network (data lives as (8, TILE_B): coordinates on
     sublanes, queries on lanes — every compare-exchange is a full-vector op),
  3. squared distances to all 232 candidates as ONE (256, 8) x (8, TILE_B)
     MXU matmul (table zero-padded to 256 rows),
  4. kernel weights f(d^2) = relu(1 - d^2/8)^4,
  5. top-32 selection as 32 unrolled masked-argmax steps (no warp shuffles on
     TPU; masked reductions are the idiom),
  6. inverse isometry + O(1) torus index encode for the selected points
     (integer row ops).

VMEM budget per tile (TILE_B = 128): queries 4 KiB, candidate table 8 KiB,
score matrix (256 x 128 f32) 128 KiB, assorted rows < 64 KiB — far under the
~16 MiB/core budget, so the grid only tiles the query axis.

The GPU original uses one thread per query with a per-thread heap; none of
that survives on TPU — see DESIGN.md §3 for the adaptation rationale.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import indexing, lattice

TILE_B = 128
NUM_PADDED = 256  # candidate table padded to an MXU-friendly row count

# Batcher odd-even mergesort network for 8 inputs (19 comparators).
SORT_NETWORK: tuple[tuple[int, int], ...] = (
    (0, 1), (2, 3), (4, 5), (6, 7),
    (0, 2), (1, 3), (4, 6), (5, 7),
    (1, 2), (5, 6),
    (0, 4), (1, 5), (2, 6), (3, 7),
    (2, 4), (3, 5),
    (1, 2), (3, 4), (5, 6),
)


def _padded_candidates() -> tuple[np.ndarray, np.ndarray]:
    cand, nsq = lattice.candidate_arrays()
    pad = NUM_PADDED - cand.shape[0]
    cand_p = np.concatenate([cand, np.zeros((pad, 8), np.float32)], 0)
    nsq_p = np.concatenate([nsq, np.zeros((pad,), np.float32)], 0)
    valid = np.concatenate(
        [np.ones((cand.shape[0],), np.float32), np.zeros((pad,), np.float32)]
    )
    return cand_p, nsq_p, valid


def _decode_d8_rows(u):
    """Nearest D8 point; u is (8, B) with coordinates on the sublane axis."""
    r = jnp.round(u)
    delta = u - r
    worst = jnp.argmax(jnp.abs(delta), axis=0)  # (B,)
    rows = jax.lax.broadcasted_iota(jnp.int32, u.shape, 0)
    onehot = (rows == worst[None, :]).astype(u.dtype)
    flip = jnp.where(delta >= 0, 1.0, -1.0)
    r_alt = r + onehot * flip
    odd = jnp.mod(jnp.sum(r, axis=0), 2.0) != 0  # (B,)
    return jnp.where(odd[None, :], r_alt, r)


def _decode_rows(q):
    even = 2.0 * _decode_d8_rows(q * 0.5)
    odd = 2.0 * _decode_d8_rows((q - 1.0) * 0.5) + 1.0
    de = jnp.sum((q - even) ** 2, axis=0)
    do = jnp.sum((q - odd) ** 2, axis=0)
    return jnp.where((de <= do)[None, :], even, odd)


def _sort_rows_desc(keys, payloads):
    """Sort 8 rows by descending key via the fixed comparator network.

    payloads is a list of (8, B) arrays permuted alongside the keys.
    """
    rows = [keys[i] for i in range(8)]
    pls = [[p[i] for i in range(8)] for p in payloads]
    for i, j in SORT_NETWORK:
        swap = rows[i] < rows[j]  # descending order
        ri, rj = rows[i], rows[j]
        rows[i] = jnp.where(swap, rj, ri)
        rows[j] = jnp.where(swap, ri, rj)
        for p in pls:
            pi, pj = p[i], p[j]
            p[i] = jnp.where(swap, pj, pi)
            p[j] = jnp.where(swap, pi, pj)
    return (
        jnp.stack(rows, axis=0),
        [jnp.stack(p, axis=0) for p in pls],
    )


def _encode_rows(x_int, K: tuple[int, ...]):
    """O(1) torus index from integer lattice coords (8, B) — see indexing.py."""
    M = [k // 2 for k in K]
    xm = [jnp.mod(x_int[i], K[i]) for i in range(8)]
    pbit = xm[0] & 1
    u = [(xm[i] - pbit) >> 1 for i in range(8)]
    qpar = functools.reduce(lambda a, b: a + b, u[:7]) & 1
    j8 = (u[7] - qpar) >> 1
    idx7 = u[0]
    for i in range(1, 7):
        idx7 = idx7 * M[i] + u[i]
    return (idx7 * (M[7] >> 1) + j8) * 2 + pbit


def _query_kernel(q_ref, cand_ref, aux_ref, idx_ref, w_ref,
                  *, K: tuple[int, ...], top_k: int):
    cand = cand_ref[...]                   # (256, 8)
    cand_nsq = aux_ref[0, :]               # (256,)
    valid = aux_ref[1, :]                  # (256,)

    q = q_ref[...].astype(jnp.float32).T   # (8, B)
    c = _decode_rows(q)
    t = q - c
    iota8 = jax.lax.broadcasted_iota(jnp.int32, t.shape, 0)
    keys, (tsort, perm) = _sort_rows_desc(jnp.abs(t), [t, iota8])
    sgn = jnp.where(tsort < 0, -1.0, 1.0)
    # product of the signs, from the count of negatives (Mosaic has no
    # reduce_prod)
    negatives = jnp.sum((tsort < 0).astype(jnp.float32), axis=0,
                        keepdims=True)
    parity = 1.0 - 2.0 * jnp.mod(negatives, 2.0)
    sgn = jnp.concatenate([sgn[:7], sgn[7:] * parity], axis=0)
    z = sgn * tsort                         # (8, B), lies in F

    # distances to all candidates: one MXU matmul
    cross = jnp.dot(cand, z, preferred_element_type=jnp.float32)  # (256, B)
    znorm = jnp.sum(z * z, axis=0, keepdims=True)                 # (1, B)
    d2 = znorm - 2.0 * cross + cand_nsq[:, None]
    relu = jnp.maximum(0.0, 1.0 - d2 / lattice.RADIUS_SQ)
    w_all = (relu * relu) * (relu * relu)
    scores = jnp.where(valid[:, None] > 0, w_all, -1.0)           # (256, B)

    idx_cols, w_cols = [], []
    for _ in range(top_k):
        m = jnp.max(scores, axis=0)                               # (B,)
        am = jnp.argmax(scores, axis=0)                           # (B,)
        rows256 = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
        onehot = (rows256 == am[None, :]).astype(jnp.float32)     # (256, B)
        # gather the selected candidate's canonical coords via MXU
        p_canon = jnp.dot(cand.T, onehot,
                          preferred_element_type=jnp.float32)     # (8, B)
        p_signed = sgn * p_canon
        # inverse permutation: g[perm_j] = p_signed_j
        g_rows = []
        for i in range(8):
            sel = (perm == i).astype(jnp.float32)
            g_rows.append(jnp.sum(sel * p_signed, axis=0))
        g = jnp.stack(g_rows, axis=0)                             # (8, B)
        k_glob = jnp.round(c + g).astype(jnp.int32)
        idx_cols.append(_encode_rows(k_glob, K))
        w_cols.append(jnp.maximum(m, 0.0))
        scores = jnp.where(onehot > 0, -1.0, scores)

    idx_ref[...] = jnp.stack(idx_cols, axis=-1)                   # (B, k)
    w_ref[...] = jnp.stack(w_cols, axis=-1)


def lram_query_pallas(
    q: jax.Array,
    spec: indexing.TorusSpec,
    top_k: int = lattice.DEFAULT_TOP_K,
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """(idx, w) = top-k lattice memory slots + kernel weights for q (..., 8).

    Non-differentiable by itself — repro.kernels.ops wraps it in the
    custom_vjp that implements the paper's analytic dw/dq backward.
    """
    lead = q.shape[:-1]
    qf = q.reshape(-1, 8).astype(jnp.float32)
    n = qf.shape[0]
    n_pad = -n % TILE_B
    qf = jnp.pad(qf, ((0, n_pad), (0, 0)))
    grid = (qf.shape[0] // TILE_B,)
    kern = functools.partial(_query_kernel, K=spec.K, top_k=top_k)
    cand_np, nsq_np, valid_np = _padded_candidates()
    cand = jnp.asarray(cand_np)
    aux = jnp.asarray(np.stack([nsq_np, valid_np]))  # (2, 256)
    idx, w = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE_B, 8), lambda i: (i, 0)),
            pl.BlockSpec((NUM_PADDED, 8), lambda i: (0, 0)),
            pl.BlockSpec((2, NUM_PADDED), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((TILE_B, top_k), lambda i: (i, 0)),
            pl.BlockSpec((TILE_B, top_k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qf.shape[0], top_k), jnp.int32),
            jax.ShapeDtypeStruct((qf.shape[0], top_k), jnp.float32),
        ],
        interpret=interpret,
        name="lram_e8_query",
    )(qf, cand, aux)
    idx = idx[:n].reshape(*lead, top_k)
    w = w[:n].reshape(*lead, top_k)
    return idx, w
