"""Pin the lattice implementation to the paper's exact constants (§2.4-2.6)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypo import given, settings, st

from repro.core import indexing, lattice


# ---------------------------------------------------------------------------
# Structure of Lambda
# ---------------------------------------------------------------------------

def test_shell_sizes_match_e8_theta_series():
    shells = lattice.shell_vectors()
    nsq = (shells**2).sum(1)
    # E8 theta series: 240 vectors of (scaled) norm^2 8, 2160 of norm^2 16
    assert (nsq == 0).sum() == 1
    assert (nsq == 8).sum() == 240
    assert (nsq == 16).sum() == 2160
    assert lattice.is_lattice_point(shells).all()


def test_minimum_distance_and_radii():
    shells = lattice.shell_vectors()
    nsq = (shells**2).sum(1)
    assert nsq[nsq > 0].min() == 8  # min distance sqrt(8)
    assert lattice.PACKING_RADIUS == pytest.approx(np.sqrt(8) / 2)
    assert lattice.COVERING_RADIUS == 2.0


def test_fundamental_region_candidates_exactly_232():
    assert lattice.candidate_table().shape == (232, lattice.DIM)


def test_candidate_distance_gap_is_clean():
    """No shell point has d(p,F)^2 within 1e-3 of the cut — the count of 232
    is robust, not a numerical accident."""
    d2 = lattice.distance_sq_to_fundamental_region(
        lattice.shell_vectors().astype(np.float64)
    )
    near_cut = np.abs(d2 - lattice.RADIUS_SQ) < 1e-3
    assert np.all(np.abs(d2[near_cut] - lattice.RADIUS_SQ) < 1e-7)
    assert (d2 < 8 - 1e-3).sum() == 232


# ---------------------------------------------------------------------------
# Decoder
# ---------------------------------------------------------------------------

def test_decode_returns_true_nearest_point(rng):
    q = rng.uniform(-20, 20, size=(500, 8)).astype(np.float32)
    c = np.asarray(lattice.decode(jnp.asarray(q)))
    assert lattice.is_lattice_point(c.astype(np.int64)).all()
    shells = lattice.shell_vectors()
    for i in range(0, 500, 7):
        pts = c[i].astype(np.int64) + shells
        d2 = ((pts - q[i]) ** 2).sum(1)
        dc = ((c[i] - q[i]) ** 2).sum()
        assert dc <= d2.min() + 1e-4


def test_decode_fixed_points():
    pts = np.array(
        [[0] * 8, [2, 2, 0, 0, 0, 0, 0, 0], [1] * 8, [4, 0, 0, 0, 0, 0, 0, 0],
         [3, 1, 1, 1, 1, 1, 1, -1]],
        dtype=np.float32,
    )
    assert lattice.is_lattice_point(pts.astype(np.int64)).all()
    out = np.asarray(lattice.decode(jnp.asarray(pts)))
    np.testing.assert_array_equal(out, pts)


@pytest.mark.slow
@settings(deadline=None, max_examples=30)
@given(st.lists(st.floats(-50, 50, width=32), min_size=8, max_size=8))
def test_decode_within_covering_radius(coords):
    q = jnp.asarray(np.array(coords, dtype=np.float32))
    c = lattice.decode(q)
    assert float(jnp.sum((q - c) ** 2)) <= lattice.COVERING_RADIUS**2 + 1e-3


# ---------------------------------------------------------------------------
# Canonicalization
# ---------------------------------------------------------------------------

def test_canonicalize_lands_in_F_and_is_isometric(rng):
    q = rng.uniform(-10, 10, size=(300, 8)).astype(np.float32)
    c = np.asarray(lattice.decode(jnp.asarray(q)))
    t = q - c
    z, perm, sgn = map(np.asarray, lattice.canonicalize(jnp.asarray(t)))
    assert np.all(np.diff(z[:, :7], axis=1) <= 1e-6)
    assert np.all(z[:, 6] >= np.abs(z[:, 7]) - 1e-6)
    assert np.all(z[:, 0] + z[:, 1] <= 2 + 1e-5)
    assert np.all(z.sum(1) <= 4 + 1e-5)
    # isometry: |z| is a permutation of |t|, and reconstruction is exact
    np.testing.assert_allclose(
        np.sort(np.abs(z), axis=1), np.sort(np.abs(t), axis=1), atol=1e-6
    )
    tp = np.take_along_axis(t, perm, axis=1)
    np.testing.assert_allclose(z, sgn * tp, atol=1e-6)
    # even number of sign flips
    assert np.all(np.prod(sgn, axis=1) > 0)


# ---------------------------------------------------------------------------
# Kernel support statistics (paper Table 1 + §2.5) — the paper's own numbers
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mc_weights():
    rng = np.random.default_rng(42)
    q = rng.uniform(0, 16, size=(60_000, 8)).astype(np.float32)
    f = jax.jit(lattice.neighbors_and_weights)
    ws = []
    for i in range(0, len(q), 20_000):
        _, w = f(jnp.asarray(q[i : i + 20_000]))
        ws.append(np.asarray(w))
    return np.concatenate(ws)


def test_kernel_support_stats(mc_weights):
    counts = (mc_weights > 0).sum(1)
    # paper Table 1 (E8 column): min 45 (m.c.), avg 64.94, max 121
    assert counts.max() <= 121
    assert counts.min() >= 40
    assert abs(counts.mean() - lattice.MEAN_SUPPORT) < 0.5
    # analytic mean = V_8(sqrt 8)/det = pi^4*4096/24/256
    assert lattice.MEAN_SUPPORT == pytest.approx(64.9393, abs=1e-3)


def test_weight_bounds(mc_weights):
    s = mc_weights.sum(1)
    # paper §2.5: 0.851 <= w(x) <= 1
    assert s.min() >= lattice.WEIGHT_LOWER_BOUND - 1e-4
    assert s.max() <= 1.0 + 1e-5


def test_top32_weight_fraction(mc_weights):
    s = mc_weights.sum(1)
    top = np.sort(mc_weights, axis=1)[:, -32:].sum(1)
    frac = top / s
    # paper §2.6: top-32 carries >=90% always, ~99.5% on average
    assert frac.min() >= 0.90
    assert frac.mean() >= 0.99


def test_weight_is_one_at_lattice_points_and_deep_holes():
    pts = np.array(
        [[0] * 8, [2, 2, 0, 0, 0, 0, 0, 0], [1] * 8,  # lattice points
         [2, 0, 0, 0, 0, 0, 0, 0], [0, 2, 0, 0, 0, 0, 0, 0]],  # deep holes
        dtype=np.float32,
    )
    _, w = lattice.neighbors_and_weights(jnp.asarray(pts))
    np.testing.assert_allclose(np.asarray(w).sum(1), 1.0, atol=1e-5)


def test_deep_hole_support_is_16_equal_weights():
    """At a deep hole, exactly 16 points at distance 2 contribute 1/16 each."""
    dh = jnp.asarray(np.array([[2, 0, 0, 0, 0, 0, 0, 0]], dtype=np.float32))
    _, w = lattice.neighbors_and_weights(dh)
    w = np.asarray(w)[0]
    nz = w[w > 0]
    assert len(nz) == 16
    np.testing.assert_allclose(nz, 1.0 / 16.0, atol=1e-6)


# ---------------------------------------------------------------------------
# Completeness: candidate pipeline == brute force
# ---------------------------------------------------------------------------

def test_neighbor_enumeration_complete(rng):
    q = rng.uniform(-8, 24, size=(100, 8)).astype(np.float32)
    nb, w = map(np.asarray, lattice.neighbors_and_weights(jnp.asarray(q)))
    for i in range(100):
        oracle_pts, oracle_d2 = lattice.brute_force_neighbors(q[i])
        got = {
            tuple(p): wi
            for p, wi in zip(nb[i].astype(np.int64), w[i])
            if wi > 0
        }
        want = {
            tuple(p): float(lattice.kernel_from_sq(jnp.asarray(d)))
            for p, d in zip(oracle_pts, oracle_d2)
        }
        assert set(got) == set(want)
        for k in got:
            assert got[k] == pytest.approx(want[k], abs=1e-5)


def test_kernel_function_values():
    assert float(lattice.kernel_from_sq(jnp.asarray(0.0))) == 1.0
    assert float(lattice.kernel_from_sq(jnp.asarray(8.0))) == 0.0
    assert float(lattice.kernel_from_sq(jnp.asarray(12.0))) == 0.0
    assert float(lattice.kernel_from_sq(jnp.asarray(4.0))) == pytest.approx(
        0.5**4
    )


# ---------------------------------------------------------------------------
# Undoing the canonical isometry: the signed-permutation product against the
# per-scalar gather it replaced
# ---------------------------------------------------------------------------

def _gather_neighbors(q):
    """The former formulation, kept as the oracle: all 232 candidates put
    back in the global frame by `argsort(perm)` and a per-scalar
    `take_along_axis`."""
    cand, cand_nsq = map(jnp.asarray, lattice.candidate_arrays())
    c = lattice.decode(q)
    z, perm, sgn = lattice.canonicalize(q - c)
    d2 = jnp.sum(z * z, axis=-1, keepdims=True) - 2.0 * (z @ cand.T) + cand_nsq
    w = lattice.kernel_from_sq(d2)
    inv = jnp.argsort(perm, axis=-1, stable=True)
    sp = sgn[..., None, :] * cand
    glob = jnp.take_along_axis(
        sp, jnp.broadcast_to(inv[..., None, :], sp.shape), axis=-1
    )
    return c[..., None, :] + glob, w


def _gather_indices_and_weights(q, spec, top_k):
    nbrs, w = _gather_neighbors(q)
    w_top, sel = jax.lax.top_k(w, top_k)
    nb_top = jnp.take_along_axis(nbrs, sel[..., None], axis=-2)
    return indexing.encode_points(nb_top, spec), w_top


def _queries(case: str) -> np.ndarray:
    rng = np.random.default_rng(14)
    if case.startswith("uniform-"):
        scale = float(case.split("-")[1])
        return rng.uniform(-scale, scale, size=(48, 3, 8)).astype(np.float32)
    spec = indexing.choose_torus(16)
    centres = indexing.decode_index(
        rng.integers(0, spec.num_locations, size=96), spec
    )
    if case == "lattice-points":
        pts = np.concatenate([centres, centres[:24] + lattice.shell_vectors()[
            rng.integers(0, 2401, size=24)]])
        return pts.astype(np.float32)
    assert case == "ties-and-signed-zeros"
    # offsets whose |t| repeat (ties in the canonical sort); the first rows
    # sit at the origin, so their coordinates are +0.0 and -0.0 themselves
    vals = np.array([0.0, -0.0, 0.25, -0.25, 0.5, -0.5, 0.75, -0.75, 1.0],
                    dtype=np.float32)
    t = vals[rng.integers(0, len(vals), size=(96, 8))]
    t[8:16] = np.float32(0.5) * np.where(rng.random((8, 8)) < 0.5, -1, 1)
    q = centres.astype(np.float32) + t
    q[:16] = t[:16]
    q[16] = np.float32(-0.0)
    return q


UNDO_CASES = ["uniform-1", "uniform-5", "uniform-50", "lattice-points",
              "ties-and-signed-zeros"]


@pytest.mark.parametrize("case", UNDO_CASES)
def test_undo_isometry_matches_gather(case):
    """All 232 neighbours are the gather's lattice points and the weights
    its bits.  Neighbours compare as numbers: a zero coordinate may come
    out as -0.0 in one and +0.0 in the other, the same lattice point."""
    q = jnp.asarray(_queries(case))
    nb, w = jax.jit(lattice.neighbors_and_weights)(q)
    nb_ref, w_ref = jax.jit(_gather_neighbors)(q)
    np.testing.assert_array_equal(np.asarray(nb), np.asarray(nb_ref))
    np.testing.assert_array_equal(np.asarray(w).view(np.uint32),
                                  np.asarray(w_ref).view(np.uint32))
    spec = indexing.choose_torus(16)
    np.testing.assert_array_equal(
        np.asarray(indexing.encode_points(nb, spec)),
        np.asarray(indexing.encode_points(nb_ref, spec)),
    )


@pytest.fixture(params=["host", "mesh"])
def branch(request):
    """`indices_and_weights` without a mesh (lax.top_k, then only the
    selected candidates put back in the global frame) and under a
    one-device mesh (all 232 put back, then masked-argmax passes)."""
    from repro.distributed import context
    from repro.launch.mesh import make_mesh

    if request.param == "mesh":
        context.set_mesh(make_mesh((1, 1), ("data", "model")))
    yield request.param
    context.set_mesh(None)


@pytest.mark.parametrize("case", UNDO_CASES)
def test_indices_and_weights_match_gather(case, branch):
    from repro.core import lram
    from repro.distributed import context

    assert (context.get_mesh() is None) == (branch == "host")
    spec = indexing.choose_torus(16)
    q = jnp.asarray(_queries(case))
    idx, w = jax.jit(lram.indices_and_weights, static_argnums=(1, 2))(
        q, spec, lattice.DEFAULT_TOP_K)
    idx_ref, w_ref = jax.jit(_gather_indices_and_weights,
                             static_argnums=(1, 2))(
        q, spec, lattice.DEFAULT_TOP_K)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx_ref))
    np.testing.assert_array_equal(np.asarray(w).view(np.uint32),
                                  np.asarray(w_ref).view(np.uint32))


def test_query_has_no_scalar_gather_undoing_the_isometry():
    """The train cell's query shape, compiled on the CPU: no gather runs
    under `lram/query/undo_isometry`, and none reads an operand of 232 x 8
    values a query (the former `take_along_axis` read 121,634,816 scalars
    one index each)."""
    from repro.core import lram

    spec = indexing.choose_torus(20)
    shape = (16, 128, 32, 8)

    def query(q):
        with jax.named_scope("lram"), jax.named_scope("query"):
            return lram.indices_and_weights(q, spec, lattice.DEFAULT_TOP_K)

    hlo = jax.jit(query).lower(
        jax.ShapeDtypeStruct(shape, jnp.float32)).compile().as_text()
    assert "lram/query/undo_isometry" in hlo
    sizes = {m.group(1): int(np.prod([int(d) for d in m.group(2).split(",")
                                      if d]))
             for m in re.finditer(r"%(\S+) = \w+\[([\d,]*)\]", hlo)}
    all_candidates = np.prod(shape[:-1]) * lattice.NUM_CANDIDATES * lattice.DIM
    for ln in re.findall(r".* gather\(.*", hlo):
        assert "lram/query/undo_isometry" not in ln, ln
        operand = re.search(r" gather\(%([^,)]+)", ln).group(1)
        assert sizes[operand] < all_candidates, ln
