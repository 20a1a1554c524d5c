"""The lookup-backend registry: the full placement × storage × kernel plan
matrix is numerically equivalent to the dense fp32 reference (eager + jit +
grad; quantized cells within `repro.quant.max_abs_error_bound`), impossible
cells raise `LookupPlanError` at resolve time, the legacy callable-hook
protocol is gone (clear error, not a silent shim), and sharded-tiered
stores train / checkpoint / serve like their single-range twins."""

import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_in_subprocess
from repro import memstore, nn, quant
from repro.checkpoint import CheckpointManager
from repro.core import lookup, lram
from repro.distributed import context as _ctx
from repro.distributed.sharded_lram import ShardedTieredStore
from repro.launch.mesh import make_mesh
from repro.memstore import TieredSpec, TieredValueStore

KEY = jax.random.PRNGKey(0)
KW = dict(log2_locations=16, m=8, heads=2, query_norm="rms")

PLACEMENTS = ("dense", "tiered", "sharded", "sharded-tiered")
STORAGES = ("fp32", "int8", "fp8")
KERNELS = ("reference", "pallas")
MATRIX = [(p, s, k) for p in PLACEMENTS for s in STORAGES for k in KERNELS]


def make_cfg(placement, storage, kernel, **extra):
    kw = dict(KW, **extra)
    kw["table_quant"] = "none" if storage == "fp32" else storage
    kw["lookup_kernel"] = kernel
    if placement == "dense":
        impl = "reference"
    elif placement == "tiered":
        impl = "tiered"
        kw.setdefault("tiered", TieredSpec(shard_rows=4096, cache_slots=4))
    elif placement == "sharded":
        impl = "sharded"
    else:
        impl = "sharded-tiered"
        kw.setdefault("tiered", TieredSpec(shard_rows=2048, cache_slots=2))
        kw.setdefault("model_shards", 4)
    return lram.LRAMConfig(interp_impl=impl, **kw)


@pytest.fixture(scope="module")
def model_mesh():
    """A 1-device mesh with a 'model' axis: enough to resolve and run the
    sharded placements in-process (the 8-fake-device equivalence lives in
    the slow subprocess tests)."""
    return make_mesh((1,), ("model",))


@pytest.fixture(scope="module")
def reference():
    """Dense fp32 reference layer + per-storage twins (same RNG draw)."""
    cfg = lram.LRAMConfig(**KW)
    params, state = lram.lram_init(KEY, cfg)
    x = jax.random.normal(KEY, (2, 3, cfg.in_dim))
    outs = {}
    grads = {}
    for storage in STORAGES:
        c = make_cfg("dense", storage, "reference")
        p, s = lram.lram_init(KEY, c)
        outs[storage] = np.asarray(lram.lram_apply(p, s, x, c)[0])
        grads[storage] = np.asarray(jax.grad(
            lambda xx: jnp.sum(lram.lram_apply(p, s, xx, c)[0] ** 2)
        )(x))
    return {"cfg": cfg, "params": params, "state": state, "x": x,
            "twin_out": outs, "twin_grad": grads}


# ---------------------------------------------------------------------------
# the plan matrix: every supported cell == the reference, eager + jit + grad
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("placement,storage,kernel", MATRIX)
def test_plan_matrix_matches_reference(placement, storage, kernel,
                                       reference, model_mesh):
    """Each cell reproduces the same-storage dense reference twin exactly
    (identical init rounding) and the fp32 reference within the documented
    quantization bound, under eager, jit, and grad-of-input."""
    cfg = make_cfg(placement, storage, kernel)
    x = reference["x"]
    y_twin = reference["twin_out"][storage]
    g_twin = reference["twin_grad"][storage]
    if placement == "sharded":
        _ctx.set_mesh(model_mesh)
    try:
        plan = lookup.resolve(cfg)
        assert plan.cell == (placement, storage, kernel)
        params, state = lram.lram_init(KEY, cfg)
        y = lram.lram_apply(params, state, x, cfg)[0]
        y_jit = jax.jit(
            lambda xx: lram.lram_apply(params, state, xx, cfg)[0]
        )(x)
        g = jax.grad(
            lambda xx: jnp.sum(lram.lram_apply(params, state, xx, cfg)[0]
                               ** 2)
        )(x)
    finally:
        _ctx.set_mesh(None)
    np.testing.assert_allclose(np.asarray(y), y_twin, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y_jit), y_twin, atol=1e-5)
    np.testing.assert_allclose(np.asarray(g), g_twin, atol=1e-4, rtol=1e-4)
    # sanity vs the fp32 twin (the hard bound is asserted at interp level
    # in test_plan_matrix_interp_error_bound)
    np.testing.assert_allclose(np.asarray(y), reference["twin_out"]["fp32"],
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("placement,storage,kernel", MATRIX)
def test_plan_matrix_interp_error_bound(placement, storage, kernel,
                                        model_mesh, rng):
    """plan.interp on a shared table draw stays within
    `quant.max_abs_error_bound` of the fp32 gather (exact for fp32)."""
    cfg = make_cfg(placement, storage, kernel)
    values = rng.normal(size=(2**16, 8)).astype(np.float32) * 0.02
    idx = jnp.asarray(rng.integers(0, 2**16, size=(16, 32)).astype(np.int32))
    w = jnp.asarray(rng.normal(size=(16, 32)).astype(np.float32))
    want = np.einsum("...k,...km->...m", np.asarray(w), values[np.asarray(idx)])
    if placement == "sharded":
        _ctx.set_mesh(model_mesh)
    try:
        plan = lookup.resolve(cfg)
        table = plan.build_table(jnp.asarray(values))
        got = np.asarray(plan.interp(table, idx, w))
    finally:
        _ctx.set_mesh(None)
    if storage == "fp32":
        np.testing.assert_allclose(got, want, atol=1e-5)
    else:
        _, scale = quant.quantize_rows_np(values, storage)
        bound = quant.max_abs_error_bound(scale, np.asarray(w), storage)
        assert np.abs(got - want).max() <= bound + 1e-6


# ---------------------------------------------------------------------------
# impossible cells fail at resolve time, not inside apply
# ---------------------------------------------------------------------------

def test_unknown_impl_raises_plan_error():
    with pytest.raises(lookup.LookupPlanError, match="unknown interp_impl"):
        lookup.resolve(lram.LRAMConfig(**KW, interp_impl="bogus"))


def test_unknown_kernel_raises_plan_error():
    with pytest.raises(lookup.LookupPlanError, match="unknown kernel"):
        lookup.resolve(lram.LRAMConfig(**KW, lookup_kernel="cuda"))


def test_sharded_without_mesh_raises_plan_error():
    assert _ctx.get_mesh() is None
    with pytest.raises(lookup.LookupPlanError, match="mesh"):
        lookup.resolve(lram.LRAMConfig(**KW, interp_impl="sharded"))


def test_sharded_tiered_indivisible_ranges_raise():
    with pytest.raises(lookup.LookupPlanError, match="not divisible"):
        lookup.resolve(lram.LRAMConfig(
            **KW, interp_impl="sharded-tiered", model_shards=3,
        ))
    with pytest.raises(lookup.LookupPlanError, match="shard_rows"):
        lookup.resolve(lram.LRAMConfig(
            **KW, interp_impl="sharded-tiered", model_shards=4,
            tiered=TieredSpec(shard_rows=32768, cache_slots=1),
        ))


def test_quant_conflict_raises_plan_error():
    with pytest.raises(lookup.LookupPlanError, match="conflicts"):
        lookup.resolve(lram.LRAMConfig(
            **KW, interp_impl="tiered", table_quant="int8",
            tiered=TieredSpec(quant="fp8"),
        ))


def test_placement_table_mismatch_raises_plan_error():
    """Init dense, apply tiered: the plan rejects the mismatched table with
    a clear error instead of crashing inside the gather."""
    cfg = lram.LRAMConfig(**KW)
    params, state = lram.lram_init(KEY, cfg)
    x = jax.random.normal(KEY, (2, cfg.in_dim))
    with pytest.raises(lookup.LookupPlanError, match="TieredValueStore"):
        lram.lram_apply(params, state, x, cfg, interp_impl="tiered")


def test_storage_table_mismatch_raises_plan_error():
    """A quantized table under an fp32 plan (and vice versa) is a clear
    LookupPlanError, not a crash deep inside the gather."""
    cfg_q = lram.LRAMConfig(**KW, table_quant="int8")
    params_q, state_q = lram.lram_init(KEY, cfg_q)
    x = jax.random.normal(KEY, (2, cfg_q.in_dim))
    cfg_fp = lram.LRAMConfig(**KW)
    with pytest.raises(lookup.LookupPlanError, match="QuantizedTable"):
        lram.lram_apply(params_q, state_q, x, cfg_fp)
    params_fp, state_fp = lram.lram_init(KEY, cfg_fp)
    with pytest.raises(lookup.LookupPlanError, match="QuantizedTable"):
        lram.lram_apply(params_fp, state_fp, x, cfg_q)


# ---------------------------------------------------------------------------
# legacy callable hooks: removed, with a clear error
# ---------------------------------------------------------------------------

def test_callable_hook_protocol_removed(reference):
    """The retired hook protocol fails loudly at resolve time — pointing
    at the registry — instead of silently bypassing the plan."""
    cfg, x = reference["cfg"], reference["x"]
    with pytest.raises(lookup.LookupPlanError, match="removed"):
        lram.lram_apply(reference["params"], reference["state"], x,
                        cfg, interp_impl=lram.gather_interp)
    assert not hasattr(lookup, "plan_from_callable")


# ---------------------------------------------------------------------------
# capability flags (what the serve engine / trainer / checkpoint read)
# ---------------------------------------------------------------------------

def test_plan_capabilities(model_mesh):
    dense = lookup.resolve(lram.LRAMConfig(**KW))
    assert not dense.supports_prefetch
    assert dense.table_update == "autodiff"
    assert dense.checkpoint_layout == "dense"
    assert dense.supports_growth and not dense.row_stats
    assert dense.table_rows_axis is None

    frozen = lookup.resolve(lram.LRAMConfig(**KW, table_quant="int8"))
    assert frozen.table_update == "frozen"
    assert frozen.supports_growth

    tiered = lookup.resolve(make_cfg("tiered", "int8", "reference"))
    assert tiered.supports_prefetch
    assert tiered.table_update == "writeback"
    assert tiered.checkpoint_layout == "shards"
    assert tiered.supports_growth and tiered.row_stats
    assert tiered.build_empty is not None

    st = lookup.resolve(make_cfg("sharded-tiered", "fp32", "reference"))
    assert st.supports_prefetch and st.table_update == "writeback"
    assert st.supports_growth and st.row_stats
    assert st.build_empty is not None

    _ctx.set_mesh(model_mesh)
    try:
        sharded = lookup.resolve(lram.LRAMConfig(**KW, interp_impl="sharded"))
    finally:
        _ctx.set_mesh(None)
    assert sharded.requires_mesh and not sharded.supports_prefetch
    # mesh-sharded dense tables reshard by relaunch, not live growth; the
    # plan emits its own pspec row axis instead of a sharding-rule regex
    assert not sharded.supports_growth
    assert sharded.table_rows_axis == "model"


@pytest.mark.slow
def test_sharded_pallas_and_quant_cells_on_real_mesh():
    """The previously-impossible sharded × pallas and sharded × int8 cells
    on an actual 8-fake-device mesh: the plan resolves, shard_maps the
    table over 4 model shards, and matches the dense fp32 reference
    (within the quant bound for int8), jit + grad included."""
    run_in_subprocess(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro import quant
        from repro.core import lookup, lram
        from repro.distributed import context as _ctx

        mesh = make_mesh((2, 4), ("data", "model"))
        _ctx.set_mesh(mesh)
        KEY = jax.random.PRNGKey(0)
        kw = dict(log2_locations=16, m=8, heads=2, query_norm="rms")
        cfg_ref = lram.LRAMConfig(**kw)
        p_ref, s_ref = lram.lram_init(KEY, cfg_ref)
        x = jax.random.normal(KEY, (4, 3, cfg_ref.in_dim))
        y_ref, _ = lram.lram_apply(p_ref, s_ref, x, cfg_ref)

        for storage, kernel in (("none", "pallas"), ("int8", "reference"),
                                ("int8", "pallas")):
            cfg = lram.LRAMConfig(**kw, interp_impl="sharded",
                                  table_quant=storage, lookup_kernel=kernel)
            plan = lookup.resolve(cfg)
            assert plan.requires_mesh
            p, s = lram.lram_init(KEY, cfg)
            y, _ = lram.lram_apply(p, s, x, cfg)
            yj = jax.jit(lambda xx: lram.lram_apply(p, s, xx, cfg)[0])(x)
            g = jax.grad(lambda xx: jnp.sum(
                lram.lram_apply(p, s, xx, cfg)[0] ** 2))(x)
            assert bool(jnp.isfinite(g).all())
            np.testing.assert_allclose(np.asarray(y), np.asarray(yj),
                                       atol=1e-5)
            if storage == "none":
                np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                           atol=1e-5)
            else:
                assert np.abs(np.asarray(y) - np.asarray(y_ref)).max() < 2e-2
            print("cell", plan.cell, "OK")
    """), devices=8)


# ---------------------------------------------------------------------------
# memffn RNG decorrelation (the k2-never-used bug)
# ---------------------------------------------------------------------------

def test_memffn_init_keys_decorrelated():
    """wi must be seeded by its own split (k2), not share k1 with the
    memory table — the old correlated init is explicitly absent."""
    width = 64
    cfg = lram.memffn_config(width, 16, query_norm="rms")
    key = jax.random.PRNGKey(7)
    params, _ = lram.memffn_init(key, width, cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    np.testing.assert_array_equal(
        np.asarray(params["wi"]["kernel"]),
        np.asarray(nn.dense_init(k2, width, width)["kernel"]),
    )
    assert not np.allclose(
        np.asarray(params["wi"]["kernel"]),
        np.asarray(nn.dense_init(k1, width, width)["kernel"]),
    )
    np.testing.assert_array_equal(
        np.asarray(params["wo"]["kernel"]),
        np.asarray(nn.dense_init(k3, 4 * width, width)["kernel"]),
    )


# ---------------------------------------------------------------------------
# sharded-tiered: training write-back, checkpoint, store discovery
# ---------------------------------------------------------------------------

def test_sharded_tiered_writeback_routes_to_owning_ranges(rng):
    dense = rng.normal(size=(4096, 8)).astype(np.float32)
    store = ShardedTieredStore.from_dense(
        dense, TieredSpec(shard_rows=256, cache_slots=2), num_ranges=4
    )
    store.writeback_lr = 0.1
    assert store.parts[2].writeback_lr == 0.1
    idx = rng.integers(0, 4096, size=(16, 8)).astype(np.int32)
    w = jnp.asarray(rng.normal(size=idx.shape).astype(np.float32))

    def loss(w_):
        return jnp.sum(
            memstore.tiered_interp(store, jnp.asarray(idx), w_) ** 2
        )

    dw = jax.grad(loss)(w)
    assert bool(jnp.isfinite(dw).all())
    after = store.to_dense()
    touched = np.zeros(4096, bool)
    touched[idx.reshape(-1)] = True
    assert not np.allclose(after[touched], dense[touched])
    np.testing.assert_array_equal(after[~touched], dense[~touched])


def test_sharded_tiered_stats_exclude_bucket_padding(rng):
    """The power-of-two padding in the routed gather is weight-0 filler —
    it must not inflate hits/misses/uncached (hit_rate feeds the table9
    rows and the serve report)."""
    dense = rng.normal(size=(4096, 8)).astype(np.float32)
    store = ShardedTieredStore.from_dense(
        dense, TieredSpec(shard_rows=256, cache_slots=4), num_ranges=2
    )
    idx = rng.integers(0, 4096, size=(11, 12)).astype(np.int32)  # 132 elems
    w = rng.normal(size=idx.shape).astype(np.float32)
    store.gather(idx, w)
    s = store.stats
    assert s["hits"] + s["misses"] + s["uncached"] == idx.size


def test_sharded_tiered_checkpoint_cross_restores(rng, tmp_path):
    """A sharded-tiered checkpoint streams global shard ids, so it restores
    bit-exact into a fresh sharded-tiered store, a plain tiered store of
    the same total layout, and a dense proto."""
    dense = rng.normal(size=(2048, 8)).astype(np.float32)
    spec = TieredSpec(shard_rows=256, cache_slots=2)
    store = ShardedTieredStore.from_dense(dense, spec, num_ranges=2)
    store.writeback_lr = 0.5
    idx = rng.integers(0, 2048, size=(64,)).astype(np.int32)
    store.gather_rows_host(idx)
    store.apply_writeback(idx, rng.normal(size=(64, 8)).astype(np.float32))
    assert any(part._dirty for part in store.parts)

    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"values": store})
    expected = store.to_dense()

    fresh = ShardedTieredStore(2048, 8, spec, num_ranges=2)
    step, _ = mgr.restore({"values": fresh})
    assert step == 1
    np.testing.assert_array_equal(fresh.to_dense(), expected)

    tiered = TieredValueStore(2048, 8, spec)
    mgr.restore({"values": tiered})
    np.testing.assert_array_equal(tiered.to_dense(), expected)

    _, r = mgr.restore({"values": jnp.zeros((2048, 8))})
    np.testing.assert_allclose(np.asarray(r["values"]), expected, atol=1e-7)

    # and the reverse: a plain tiered checkpoint into a sharded-tiered store
    mgr2 = CheckpointManager(str(tmp_path / "t"))
    mgr2.save(1, {"values": tiered})
    fresh2 = ShardedTieredStore(2048, 8, spec, num_ranges=2)
    mgr2.restore({"values": fresh2})
    np.testing.assert_array_equal(fresh2.to_dense(), expected)


def test_find_stores_covers_sharded_tiered(rng):
    store = ShardedTieredStore.from_dense(
        rng.normal(size=(1024, 8)).astype(np.float32),
        TieredSpec(shard_rows=128, cache_slots=2), num_ranges=2,
    )
    tree = {"a": jnp.ones((2,)), "values": store}
    assert lookup.find_stores(tree) == [("values", store)]
    assert memstore.find_stores(tree) == [("values", store)]
    # leafless pytree node: invisible to tree maps
    mapped = jax.tree.map(lambda x: x * 2, tree)
    assert mapped["values"] is store


def test_sharded_tiered_config_and_engine_discovery():
    """The lram-sharded-tiered arch resolves through the registry, and the
    serve engine discovers its prefetch handles via plan capabilities."""
    from repro import configs
    from repro.models import transformer
    from repro.serving import EngineConfig, ServeEngine, synthetic_trace

    cfg = configs.get_smoke_config("lram-sharded-tiered")
    plan = lookup.resolve(cfg.lram)
    assert plan.placement == "sharded-tiered"
    assert plan.supports_prefetch

    params, state = transformer.init(jax.random.PRNGKey(0), cfg)
    found = lookup.find_stores(params)
    assert len(found) == 1
    store = found[0][1]
    assert isinstance(store, ShardedTieredStore)
    assert store.num_ranges == 2

    engine = ServeEngine(params, state, cfg,
                         EngineConfig(slots=2, max_len=24))
    assert [s for _, s in engine.stores] == [store]
    trace = synthetic_trace(np.random.default_rng(0), 3,
                            vocab_size=cfg.vocab_size, max_prompt=8,
                            max_gen=4)
    report = engine.run(trace)
    assert report.generated_tokens > 0
    assert report.cache is not None and "hit_rate" in report.cache
    assert all(r.cache_hit_rate is not None for r in report.requests)
