"""The value table's gradient (`repro.kernels.scatter_add`): the sorted,
block-owned Pallas kernel in interpret mode against XLA's `.at[].add`, the
reference gather's custom VJP against plain autodiff, which path a
backward is built with, and the sum over a mesh's batch shares."""

import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import run_in_subprocess

from repro import obs
from repro.core import lookup
from repro.kernels import scatter_add


def _xla(rows, idx, w, g):
    wg = w[..., None] * g[..., None, :]
    return jnp.zeros((rows, g.shape[-1]), jnp.float32).at[
        idx.reshape(-1)].add(wg.reshape(-1, g.shape[-1]))


def _ids(case, rng, rows, tokens, k):
    shape = (tokens, k)
    if case == "zipf":
        return np.minimum(rng.zipf(1.2, size=shape) - 1, rows - 1)
    if case == "one_row":
        return np.full(shape, 37)
    if case == "empty_blocks":  # only the first and the last block's rows
        return np.where(rng.random(shape) < 0.5,
                        rng.integers(0, 64, shape),
                        rng.integers(rows - 64, rows, shape))
    if case == "ends":
        return np.where(rng.random(shape) < 0.5, 0, rows - 1)
    if case == "out_of_range":  # negative ids wrap, ids past the end drop
        return rng.integers(-rows // 2, rows + rows // 2, shape)
    return rng.integers(0, rows, shape)


# (ids, rows, m, tokens, k): entry counts of whole chunks and not
@pytest.mark.parametrize("case,rows,m,tokens,k", [
    ("zipf", 1024, 64, 40, 32),
    ("one_row", 1024, 64, 40, 32),
    ("empty_blocks", 1024, 64, 40, 32),
    ("ends", 1024, 64, 16, 32),
    ("uniform", 1024, 64, 125, 8),
    ("zipf", 512, 128, 17, 8),
    ("one_row", 512, 128, 61, 16),
    ("out_of_range", 1024, 64, 40, 32),
])
def test_kernel_matches_xla_scatter_add(rng, case, rows, m, tokens, k):
    b, c = scatter_add.tiles(rows, m, tokens * k)
    assert rows % b == 0 and c % 128 == 0
    idx = jnp.asarray(_ids(case, rng, rows, tokens, k), jnp.int32)
    w = jnp.asarray(rng.normal(size=(tokens, k)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(tokens, m)), jnp.float32)
    got = jax.jit(lambda i, ww, gg: scatter_add.scatter_add_rows(
        rows, i, ww, gg, interpret=True))(idx, w, g)
    want = _xla(rows, idx, w, g)
    assert got.shape == (rows, m) and got.dtype == jnp.float32
    # float32 sums in another order: a few ulps of the largest partial sum
    scale = float(jnp.max(jnp.abs(want))) + 1.0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5 * scale)


@pytest.fixture
def counters():
    obs.configure()
    yield lambda: {n: v for n, v in obs.registry().counter_values().items()
                   if n.startswith("lram.scatter_add.")}
    obs.disable()


@pytest.mark.parametrize("path", ["fallback", "kernel"])
def test_reference_gather_grad_is_plain_autodiff(rng, path):
    """The registry's `reference` fp32 gather, as the CPU backend builds
    it (XLA's scatter), and with the kernel interpreted."""
    gather = (lookup.kernel_gather("reference", "fp32") if path == "fallback"
              else lambda v, i, ww: scatter_add.gather_rows(v, i, ww, True))
    rows, m = 1024, 64
    values = jnp.asarray(rng.normal(size=(rows, m)), jnp.float32)
    idx = jnp.asarray(np.minimum(rng.zipf(1.2, size=(3, 20, 32)) - 1,
                                 rows - 1), jnp.int32)
    w = jnp.asarray(rng.random((3, 20, 32)), jnp.float32)
    cot = jnp.asarray(rng.normal(size=(3, 20, m)), jnp.float32)

    def plain(v, ww):
        rows_ = jnp.take(v, idx, axis=0)
        return jnp.sum(jnp.einsum("...k,...km->...m", ww, rows_) * cot)

    def gathered(v, ww):
        return jnp.sum(gather(v, idx, ww) * cot)

    want = jax.jit(jax.grad(plain, argnums=(0, 1)))(values, w)
    got = jax.jit(jax.grad(gathered, argnums=(0, 1)))(values, w)
    np.testing.assert_allclose(np.asarray(gathered(values, w)),
                               np.asarray(plain(values, w)), rtol=1e-6)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("rows,m", [(1024, 48), (1000, 64)])
def test_untileable_shape_falls_back_to_xla(rng, counters, rows, m):
    assert scatter_add.tiles(rows, m, 640) is None
    idx = jnp.asarray(rng.integers(0, rows, (20, 32)), jnp.int32)
    w = jnp.asarray(rng.normal(size=(20, 32)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(20, m)), jnp.float32)
    got = jax.jit(lambda i, ww, gg: scatter_add.scatter_add_rows(
        rows, i, ww, gg, interpret=True))(idx, w, g)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(_xla(rows, idx, w, g)))
    assert counters() == {"lram.scatter_add.fallback": 1}


def test_counters_name_the_path_each_backward_was_built_with(
        rng, counters):
    idx = jnp.asarray(rng.integers(0, 1024, (20, 32)), jnp.int32)
    w = jnp.asarray(rng.normal(size=(20, 32)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(20, 64)), jnp.float32)

    def build(interpret):
        return jax.jit(lambda i, ww, gg: scatter_add.scatter_add_rows(
            1024, i, ww, gg, interpret=interpret))(idx, w, g)

    want = build(None)  # the CPU backend: XLA's scatter
    assert counters() == {"lram.scatter_add.fallback": 1}
    got = build(True)  # the kernel, interpreted
    assert counters() == {"lram.scatter_add.fallback": 1,
                          "lram.scatter_add.kernel": 1}
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("batch", [8, 3])
def test_mesh_adds_each_devices_share_of_the_batch(batch):
    """Under a 2 x 2 (data, model) mesh the kernel runs in a shard_map:
    with a batch the data axis divides, each device sums its share and a
    psum adds them; with one it does not, every device sums it all.  Both
    equal XLA's scatter, and so does the reference gather's gradient."""
    run_in_subprocess(textwrap.dedent(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed import context
        from repro.kernels import ref, scatter_add
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((2, 2), ("data", "model"))
        context.set_mesh(mesh)
        rng = np.random.default_rng(0)
        rows, m, b = 1024, 64, {batch}
        idx = jnp.asarray(np.minimum(rng.zipf(1.2, (b, 6, 32)) - 1,
                                     rows - 1), jnp.int32)
        w = jnp.asarray(rng.normal(size=(b, 6, 32)), jnp.float32)
        g = jnp.asarray(rng.normal(size=(b, 6, m)), jnp.float32)
        values = jnp.asarray(rng.normal(size=(rows, m)), jnp.float32)
        data = NamedSharding(mesh, P("data" if b % 2 == 0 else None))
        idx, w, g = (jax.device_put(x, data) for x in (idx, w, g))

        add = jax.jit(lambda i, ww, gg: scatter_add.scatter_add_rows(
            rows, i, ww, gg, interpret=True))
        text = add.lower(idx, w, g).as_text()
        assert "shard_map" in text or "sdy.manual_computation" in text
        assert (b % 2 == 0) == ("all_reduce" in text or "all-reduce" in text)
        want = jnp.zeros((rows, m)).at[idx.reshape(-1)].add(
            (w[..., None] * g[..., None, :]).reshape(-1, m))
        np.testing.assert_allclose(np.asarray(add(idx, w, g)),
                                   np.asarray(want), rtol=1e-5, atol=1e-4)

        def loss(gather, v, ww):
            return jnp.sum(gather(v, idx, ww) * g)
        got = jax.jit(jax.grad(lambda v, ww: loss(
            lambda *a: scatter_add.gather_rows(*a, True), v, ww),
            argnums=(0, 1)))(values, w)
        plain = jax.jit(jax.grad(lambda v, ww: loss(
            ref.gather_interp_ref, v, ww), argnums=(0, 1)))(values, w)
        for a, p in zip(got, plain):
            np.testing.assert_allclose(np.asarray(a), np.asarray(p),
                                       rtol=1e-5, atol=1e-4)
        print("ok")
    """), devices=4)
