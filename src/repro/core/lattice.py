"""The 2*E8 lattice: decoding, canonicalization, and the 232-candidate table.

The paper ("Differentiable Random Access Memory using Lattices", Goucher &
Troll 2021, §2.4-2.6) works with a copy of the E8 lattice scaled by 2 so that
all lattice points have integer coordinates:

    Lambda := { x in (2Z)^8 ∪ (2Z+1)^8  :  sum(x) ≡ 0 (mod 4) }

Equivalently Lambda = 2*D8 ∪ (2*D8 + 1) where D8 = {u in Z^8 : sum(u) even}.
Key constants (all asserted in tests against the paper):

  * minimum distance between lattice points:  sqrt(8)
  * packing radius sqrt(2), covering radius 2
  * kernel  f(r) = max(0, 1 - r^2/8)^4  vanishes exactly at the minimum
    distance, so phi(k) = v_k at every lattice point
  * exactly 232 lattice points lie within distance < sqrt(8) of the
    fundamental region F (paper §2.6)
  * average kernel-support size = V_8(sqrt 8)/det = pi^4*4096/24/256 = 64.94

This module provides BOTH the exact numpy precomputation (candidate table,
used once at import of the table) and the batched jax ops used inside the
neural network (decode / canonicalize / neighbor enumeration).  Everything is
branch-free and lane-parallel: this is the TPU-native adaptation of the
paper's per-thread CUDA decoder (see DESIGN.md §3).
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Constants
# ---------------------------------------------------------------------------

DIM = 8
#: squared kernel radius == squared minimum distance of the lattice
RADIUS_SQ = 8.0
PACKING_RADIUS = np.sqrt(2.0)
COVERING_RADIUS = 2.0
#: determinant (covolume) of the scaled lattice: 2^8 * det(E8) = 256
DET = 256
#: number of lattice points within sqrt(8) of the fundamental region (paper)
NUM_CANDIDATES = 232
#: paper keeps only the top-32 closest points (>=90% of kernel mass)
DEFAULT_TOP_K = 32
#: lower bound for the total kernel weight, (22158 - 625*sqrt(5))/24389
WEIGHT_LOWER_BOUND = (22158.0 - 625.0 * np.sqrt(5.0)) / 24389.0  # ~0.8507
#: analytic mean number of support points: V8(sqrt8)/DET
MEAN_SUPPORT = float(np.pi**4 * (8.0**4) / 24.0 / DET)  # 64.939...


# ---------------------------------------------------------------------------
# Exact shell enumeration (numpy, used for the candidate-table precompute and
# as a brute-force oracle in tests)
# ---------------------------------------------------------------------------

def _shell8() -> np.ndarray:
    """All 240 lattice vectors with squared norm 8."""
    out = []
    # even type: two coordinates +-2, rest 0  -> C(8,2)*4 = 112
    for i, j in itertools.combinations(range(DIM), 2):
        for si in (2, -2):
            for sj in (2, -2):
                v = np.zeros(DIM, dtype=np.int64)
                v[i], v[j] = si, sj
                out.append(v)
    # odd type: (+-1)^8 with an even number of minus signs -> 128
    for signs in itertools.product((1, -1), repeat=DIM):
        if signs.count(-1) % 2 == 0:
            out.append(np.array(signs, dtype=np.int64))
    arr = np.stack(out)
    assert arr.shape == (240, DIM)
    return arr


def _shell16() -> np.ndarray:
    """All 2160 lattice vectors with squared norm 16."""
    out = []
    # (+-4, 0^7) -> 16
    for i in range(DIM):
        for s in (4, -4):
            v = np.zeros(DIM, dtype=np.int64)
            v[i] = s
            out.append(v)
    # four coordinates +-2 -> C(8,4)*16 = 1120  (sum always ≡ 0 mod 4)
    for pos in itertools.combinations(range(DIM), 4):
        for signs in itertools.product((2, -2), repeat=4):
            v = np.zeros(DIM, dtype=np.int64)
            for p, s in zip(pos, signs):
                v[p] = s
            out.append(v)
    # (+-3, +-1^7) with sum ≡ 0 mod 4 -> 8*128 = 1024
    for i in range(DIM):
        for signs in itertools.product((1, -1), repeat=DIM):
            v = np.array(signs, dtype=np.int64)
            v[i] *= 3
            if v.sum() % 4 == 0:
                out.append(v)
    arr = np.stack(out)
    assert arr.shape == (2160, DIM), arr.shape
    return arr


@functools.lru_cache(maxsize=None)
def shell_vectors() -> np.ndarray:
    """All 2401 lattice vectors with squared norm <= 16 (shells 0, 8, 16).

    Any lattice point within sqrt(8) of the fundamental region F (whose
    points have norm <= covering radius 2) has norm < 2 + sqrt(8) < sqrt(24),
    hence lies in one of these shells.
    """
    return np.concatenate(
        [np.zeros((1, DIM), dtype=np.int64), _shell8(), _shell16()], axis=0
    )


def is_lattice_point(x: np.ndarray) -> np.ndarray:
    """Boolean mask: is x (integer array, (..., 8)) a point of Lambda."""
    x = np.asarray(x)
    par = np.mod(x, 2)
    same_parity = np.all(par == par[..., :1], axis=-1)
    sum_ok = np.mod(x.sum(axis=-1), 4) == 0
    return same_parity & sum_ok


# ---------------------------------------------------------------------------
# Fundamental region F and the exact candidate table
#
# F = { z : z1>=z2>=...>=z7>=|z8|,  z1+z2 <= 2,  sum(z) <= 4 }
# (paper §2.6).  We compute, for every shell vector p, the exact Euclidean
# distance d(p, F) by enumerating KKT active sets of the projection QP
# min ||x-p||^2 s.t. A x <= b  -- exact up to numerical linear algebra,
# no iterative solver involved.
# ---------------------------------------------------------------------------

def _halfspaces() -> tuple[np.ndarray, np.ndarray]:
    A, b = [], []
    for i in range(7):  # z_{i+1} - z_i <= 0  (includes z8 <= z7)
        row = np.zeros(DIM)
        row[i + 1], row[i] = 1.0, -1.0
        A.append(row)
        b.append(0.0)
    row = np.zeros(DIM)  # -z7 - z8 <= 0
    row[6], row[7] = -1.0, -1.0
    A.append(row)
    b.append(0.0)
    row = np.zeros(DIM)  # z1 + z2 <= 2
    row[0], row[1] = 1.0, 1.0
    A.append(row)
    b.append(2.0)
    A.append(np.ones(DIM))  # sum z <= 4
    b.append(4.0)
    return np.stack(A), np.array(b)


def distance_sq_to_fundamental_region(points: np.ndarray) -> np.ndarray:
    """Exact squared distance from each point (M, 8) to the polytope F.

    Enumerates all 2^10 subsets of active constraints; for each, solves the
    equality-constrained projection in closed form and keeps KKT-valid
    solutions.  The projection onto a convex set is unique, so any valid
    active set yields the answer.
    """
    A, b = _halfspaces()
    m = A.shape[0]
    pts = np.asarray(points, dtype=np.float64)
    best = np.full(pts.shape[0], np.inf)
    feas_tol, dual_tol = 1e-9, -1e-9
    all_resid = pts @ A.T - b  # (M, m)
    # empty active set: point already in F
    inside = np.all(all_resid <= feas_tol, axis=1)
    best[inside] = 0.0
    for r in range(1, m + 1):
        for subset in itertools.combinations(range(m), r):
            S = list(subset)
            As = A[S]  # (r, 8)
            G = As @ As.T
            Ginv = np.linalg.pinv(G)
            resid = all_resid[:, S]  # (M, r)
            lam = resid @ Ginv.T  # (M, r)
            if r > DIM:  # can't have >8 independent constraints
                pass
            x = pts - lam @ As  # (M, 8)
            # validity: dual feasible, primal feasible, equality consistent
            ok = np.all(lam >= dual_tol, axis=1)
            ok &= np.all(x @ A.T - b <= feas_tol, axis=1)
            ok &= np.all(np.abs(x @ As.T - b[S]) <= 1e-7, axis=1)
            d2 = ((pts - x) ** 2).sum(axis=1)
            best = np.where(ok, np.minimum(best, d2), best)
    assert np.all(np.isfinite(best)), "projection failed for some point"
    return best


@functools.lru_cache(maxsize=None)
def candidate_table() -> np.ndarray:
    """The (232, 8) int table of lattice points within < sqrt(8) of F.

    This is the paper's precomputed array (§2.6): for a canonicalized query
    z in F, every lattice point within the kernel radius of z appears here.
    Sorted lexicographically for determinism.
    """
    shells = shell_vectors()
    d2 = distance_sq_to_fundamental_region(shells.astype(np.float64))
    keep = d2 < RADIUS_SQ - 1e-7
    cands = shells[keep]
    order = np.lexsort(cands.T[::-1])
    cands = cands[order]
    assert cands.shape == (NUM_CANDIDATES, DIM), (
        f"expected {NUM_CANDIDATES} candidates, got {cands.shape[0]}"
    )
    return cands


@functools.lru_cache(maxsize=None)
def candidate_arrays() -> tuple[np.ndarray, np.ndarray]:
    """float32 candidate table and its squared norms (for the MXU matmul)."""
    c = candidate_table().astype(np.float32)
    return c, (c * c).sum(axis=1)


# ---------------------------------------------------------------------------
# Kernel function (paper §2.5)
# ---------------------------------------------------------------------------

def kernel_from_sq(d2: jax.Array) -> jax.Array:
    """f(r) = max(0, 1 - r^2/8)^4 computed from the squared distance."""
    t = jnp.maximum(0.0, 1.0 - d2 / RADIUS_SQ)
    t2 = t * t
    return t2 * t2


# ---------------------------------------------------------------------------
# Nearest-point decoding (Conway & Sloane), batched & branch-free
# ---------------------------------------------------------------------------

def _decode_d8(u: jax.Array) -> jax.Array:
    """Nearest point of D8 = {x in Z^8 : sum(x) even} to u (..., 8)."""
    r = jnp.round(u)
    delta = u - r  # in [-0.5, 0.5]
    # If the coordinate-wise rounding has odd sum, re-round the coordinate
    # with the largest rounding error to the next-nearest integer.
    worst = jnp.argmax(jnp.abs(delta), axis=-1)
    flip = jnp.where(delta >= 0, 1.0, -1.0)
    onehot = jax.nn.one_hot(worst, DIM, dtype=u.dtype)
    r_alt = r + onehot * jnp.take_along_axis(
        flip, worst[..., None], axis=-1
    )
    odd = jnp.mod(jnp.sum(r, axis=-1), 2.0) != 0
    return jnp.where(odd[..., None], r_alt, r)


def decode(q: jax.Array) -> jax.Array:
    """Nearest point of Lambda = 2*D8 ∪ (2*D8+1) to q (..., 8).

    Exact: decodes both cosets and keeps the closer one.
    """
    even = 2.0 * _decode_d8(q * 0.5)
    odd = 2.0 * _decode_d8((q - 1.0) * 0.5) + 1.0
    de = jnp.sum((q - even) ** 2, axis=-1)
    do = jnp.sum((q - odd) ** 2, axis=-1)
    return jnp.where((de <= do)[..., None], even, odd)


def canonicalize(t: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Map a Voronoi-cell offset t = q - decode(q) into F.

    Returns (z, perm, sgn) with  z_j = sgn_j * t[perm_j]  in F:
      * coordinates sorted by decreasing absolute value,
      * first seven nonnegative; the last carries the sign parity (the
        isometry group only allows an even number of sign flips).
    """
    at = jnp.abs(t)
    # The permutation is piecewise-constant in t, so sorting under
    # stop_gradient is exact a.e. (and avoids the non-differentiable
    # sort-gradient path entirely).
    perm = jnp.argsort(-jax.lax.stop_gradient(at), axis=-1, stable=True)
    tp = jnp.take_along_axis(t, perm, axis=-1)
    sgn = jnp.where(tp < 0, -1.0, 1.0).astype(t.dtype)
    parity = jnp.prod(sgn, axis=-1, keepdims=True)  # (-1)^{#negatives}
    sgn = sgn.at[..., 7:8].multiply(parity)
    z = sgn * tp
    return z, perm, sgn


def neighbors_and_weights(
    q: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """All 232 candidate lattice points near q, with kernel weights.

    Args:
      q: (..., 8) query points (any reals; torus reduction happens at
        indexing time since the kernel radius is < half the wrap period).

    Returns:
      neighbors: (..., 232, 8) lattice points (global, un-wrapped coords)
      weights:   (..., 232) kernel weights f(d(q, k)); zero outside support.

    Differentiable in q almost everywhere: the isometry (decode / perm /
    signs) is locally constant, distances are computed in the canonical
    frame where they are smooth functions of q.

    Its phases run under the named scopes `decode` (nearest point and
    canonical frame), `distance` and `undo_isometry`.
    """
    c, perm, sgn, w = canonical_weights(q)
    cand = jnp.asarray(candidate_arrays()[0], dtype=q.dtype)
    with jax.named_scope("undo_isometry"):
        return undo_isometry(cand, c, perm, sgn), w


def canonical_weights(
    q: jax.Array,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Nearest point, canonical isometry and the 232 candidates' weights.

    Returns (c, perm, sgn, w): `c` = decode(q), `canonicalize`'s `perm`
    and `sgn` of q - c, and w (..., 232), the kernel weight of each row of
    `candidate_table()` from the canonical point.  The isometry keeps
    distances, so that is the weight from q of the global point that
    `undo_isometry` maps the row to.
    """
    cand, cand_nsq = candidate_arrays()
    cand = jnp.asarray(cand, dtype=q.dtype)
    cand_nsq = jnp.asarray(cand_nsq, dtype=q.dtype)
    with jax.named_scope("decode"):
        c = decode(q)
        z, perm, sgn = canonicalize(q - c)
    with jax.named_scope("distance"):
        # squared distances to all candidates: one (..., 8) @ (8, 232) matmul
        d2 = (
            jnp.sum(z * z, axis=-1, keepdims=True)
            - 2.0 * (z @ cand.T)
            + cand_nsq
        )
        w = kernel_from_sq(d2)
    return c, perm, sgn, w


def undo_isometry(
    points: jax.Array, c: jax.Array, perm: jax.Array, sgn: jax.Array
) -> jax.Array:
    """Canonical-frame points back to global lattice points.

    `canonicalize` maps t to z with z_j = sgn_j * t[perm_j], so a
    canonical point p is the global point c + p @ S, with S the signed
    permutation matrix S[l, i] = sgn_l * [perm_l == i].

    Args:
      points: (n, 8) points shared by every query (the candidate table)
        or (..., n, 8) points of each query.
      c, perm, sgn: (..., 8) from `decode` and `canonicalize`.

    Returns:
      (..., n, 8) global points.

    The product is exact: coordinates are small integers and every output
    has one nonzero term, so `HIGHEST` only keeps a lower default matmul
    precision from rounding the operands.
    """
    s = sgn[..., :, None] * jax.nn.one_hot(perm, DIM, dtype=sgn.dtype)
    glob = jnp.einsum(
        "...kl,...li->...ki", points, s, precision=jax.lax.Precision.HIGHEST
    )
    return c[..., None, :] + glob


def brute_force_neighbors(q: np.ndarray, radius_sq: float = RADIUS_SQ):
    """Oracle: all lattice points within sqrt(radius_sq) of a single query.

    Exhaustive over the <=sqrt(24) shells around the decoded center; used in
    tests to certify the candidate-table pipeline is complete.
    """
    q = np.asarray(q, dtype=np.float64)
    c = np.asarray(decode(jnp.asarray(q))).astype(np.int64)
    pts = c + shell_vectors()
    d2 = ((pts - q) ** 2).sum(axis=1)
    return pts[d2 < radius_sq - 1e-9], d2[d2 < radius_sq - 1e-9]
