"""The memory lifecycle manager (`repro.memctl`): telemetry counters,
online growth (append-only, exact at pre-growth points for every storage
kind, eager + jit + grad), live plan-to-plan migration (round-trip exact),
the controller's train-step and serve-tick policy loops, and the
plan-driven sharding rules that replaced the memory-table regex."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs, memctl, quant
from repro.core import indexing, lookup, lram
from repro.distributed import context as _ctx, sharding
from repro.distributed.sharded_lram import ShardedTieredStore
from repro.launch.mesh import make_mesh
from repro.memstore import TieredSpec, TieredValueStore
from repro.models import transformer

KEY = jax.random.PRNGKey(0)
KW = dict(log2_locations=16, m=8, heads=2, query_norm="rms")

GROW_CELLS = [
    (p, s)
    for p in ("dense", "tiered", "sharded-tiered")
    for s in ("fp32", "int8", "fp8")
]


def make_cfg(placement, storage, **extra):
    kw = dict(KW, **extra)
    kw["table_quant"] = "none" if storage == "fp32" else storage
    if placement == "dense":
        return lram.LRAMConfig(interp_impl="reference", **kw)
    if placement == "tiered":
        kw.setdefault("tiered", TieredSpec(shard_rows=4096, cache_slots=4))
        return lram.LRAMConfig(interp_impl="tiered", **kw)
    kw.setdefault("tiered", TieredSpec(shard_rows=2048, cache_slots=2))
    kw.setdefault("model_shards", 4)
    return lram.LRAMConfig(interp_impl="sharded-tiered", **kw)


# ---------------------------------------------------------------------------
# the growth math: index preservation and the coarse-lattice parent rule
# ---------------------------------------------------------------------------

def test_grow_torus_preserves_old_indices():
    old = indexing.choose_torus(16)
    new = indexing.grow_torus(old, 2)
    assert new.num_locations == 2 * old.num_locations
    ids = np.arange(old.num_locations)
    pts = indexing.decode_index(ids, old)
    np.testing.assert_array_equal(
        np.asarray(indexing.encode_points(jnp.asarray(pts), new)), ids
    )


def test_growth_parents_is_alias_rule():
    """For K_0 enlargements, the lattice-derived parent mapping reduces to
    j mod old_N (the grown table is an alias stack of the old one)."""
    old = indexing.choose_torus(16)
    for factor in (2, 4):
        new = indexing.grow_torus(old, factor)
        n_old, n_new = old.num_locations, new.num_locations
        parents = indexing.growth_parents(old, new, n_old, n_new)
        np.testing.assert_array_equal(
            parents, np.arange(n_old, n_new) % n_old
        )


def test_grow_torus_rejects_bad_factor():
    spec = indexing.choose_torus(16)
    with pytest.raises(ValueError, match="power of two"):
        indexing.grow_torus(spec, 3)
    with pytest.raises(ValueError, match="multiples"):
        indexing.growth_parents(indexing.grow_torus(spec, 2), spec, 0, 1)


def test_lram_config_torus_override_validated():
    spec = indexing.grow_torus(indexing.choose_torus(16), 2)
    cfg = lram.LRAMConfig(**dict(KW, log2_locations=17), torus=spec)
    assert cfg.torus_spec == spec
    with pytest.raises(ValueError, match="locations"):
        lram.LRAMConfig(**KW, torus=spec)  # 2^17 torus vs log2=16


# ---------------------------------------------------------------------------
# growth equivalence: every placement x storage, eager + jit + grad
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("placement,storage", GROW_CELLS)
def test_grow_reproduces_pre_growth_points(placement, storage, rng):
    """After grow(N -> 2N), lookups at pre-growth *points* (the same
    geometric query positions, re-encoded on the grown torus) match the
    pre-growth outputs to float rounding — for every storage kind, the
    appended rows are bit-copies of their coarse-lattice parents."""
    cfg = make_cfg(placement, storage)
    params, state = lram.lram_init(KEY, cfg)
    plan = lookup.resolve(cfg)
    q = jnp.asarray(rng.uniform(0, 8, size=(16, 8)).astype(np.float32))
    idx_o, w = lram.indices_and_weights(q, cfg.torus_spec, cfg.top_k)
    y_pre = np.asarray(plan.interp(params["values"], idx_o, w))
    g_pre = np.asarray(jax.grad(
        lambda ww: jnp.sum(plan.interp(params["values"], idx_o, ww) ** 2)
    )(w))

    params2, cfg2 = memctl.grow(params, cfg, 2 ** 17)
    assert cfg2.num_locations == 2 ** 17
    plan2 = lookup.resolve(cfg2)
    idx_n, w_n = lram.indices_and_weights(q, cfg2.torus_spec, cfg2.top_k)
    np.testing.assert_array_equal(np.asarray(w_n), np.asarray(w))

    y_post = np.asarray(plan2.interp(params2["values"], idx_n, w))
    y_jit = np.asarray(jax.jit(
        lambda i, ww: plan2.interp(params2["values"], i, ww)
    )(idx_n, w))
    g_post = np.asarray(jax.grad(
        lambda ww: jnp.sum(plan2.interp(params2["values"], idx_n, ww) ** 2)
    )(w))
    np.testing.assert_allclose(y_post, y_pre, atol=1e-6)
    np.testing.assert_allclose(y_jit, y_pre, atol=1e-6)
    np.testing.assert_allclose(g_post, g_pre, atol=1e-5)


def test_grow_rejects_bad_sizes_and_sharded():
    cfg = make_cfg("dense", "fp32")
    params, _ = lram.lram_init(KEY, cfg)
    with pytest.raises(ValueError, match="multiple"):
        memctl.grow(params, cfg, 2 ** 16 + 4096)
    with pytest.raises(ValueError, match="grow"):
        memctl.grow(params, cfg, 2 ** 15)
    mesh = make_mesh((1,), ("model",))
    _ctx.set_mesh(mesh)
    try:
        cfg_sh = lram.LRAMConfig(**KW, interp_impl="sharded")
        p_sh, _ = lram.lram_init(KEY, cfg_sh)
        with pytest.raises(lookup.LookupPlanError, match="grow"):
            memctl.grow(p_sh, cfg_sh, 2 ** 17)
    finally:
        _ctx.set_mesh(None)


def test_tiered_grow_appends_without_touching_cache(rng):
    """Growth appends host shards in place: the device cache keeps its
    residency (no invalidation, no new fills) and old shard ids stay
    valid; post-growth lookups of old rows are bit-identical."""
    cfg = make_cfg("tiered", "fp32")
    params, _ = lram.lram_init(KEY, cfg)
    store = params["values"]
    assert isinstance(store, TieredValueStore)
    idx = rng.integers(0, 2 ** 16, size=(8, 4)).astype(np.int32)
    w = rng.normal(size=idx.shape).astype(np.float32)
    y_pre = np.asarray(store.gather(idx, w))
    resident = store.resident_shards()
    fills = store.stats["fills"]

    params2, cfg2 = memctl.grow(params, cfg, 2 ** 17)
    assert params2["values"] is store  # in place: handles stay valid
    assert store.num_rows == 2 ** 17
    assert store.resident_shards() == resident
    assert store.stats["fills"] == fills
    np.testing.assert_array_equal(np.asarray(store.gather(idx, w)), y_pre)
    # appended rows alias their parents (j mod old_N)
    hi = idx + 2 ** 16
    np.testing.assert_array_equal(np.asarray(store.gather(hi, w)), y_pre)


def test_tiered_grow_trains_after_growth(rng):
    """Write-back still lands after growth — including into appended rows
    — and dirty state flushes through the grown host tier."""
    from repro import memstore

    cfg = make_cfg("tiered", "fp32")
    params, _ = lram.lram_init(KEY, cfg)
    store = params["values"]
    _, cfg2 = memctl.grow(params, cfg, 2 ** 17)
    store.writeback_lr = 0.1
    idx = rng.integers(0, 2 ** 17, size=(16, 4)).astype(np.int32)
    w = jnp.asarray(rng.normal(size=idx.shape).astype(np.float32))
    before = store.to_dense()

    def loss(w_):
        return jnp.sum(memstore.tiered_interp(store, jnp.asarray(idx), w_)
                       ** 2)

    jax.grad(loss)(w)
    after = store.to_dense()
    touched = np.zeros(2 ** 17, bool)
    touched[idx.reshape(-1)] = True
    assert not np.allclose(after[touched], before[touched])
    np.testing.assert_array_equal(after[~touched], before[~touched])


def test_sharded_tiered_grow_appends_ranges(rng):
    cfg = make_cfg("sharded-tiered", "fp32")
    params, _ = lram.lram_init(KEY, cfg)
    store = params["values"]
    assert isinstance(store, ShardedTieredStore)
    store.writeback_lr = 0.25
    before = store.to_dense()
    params2, cfg2 = memctl.grow(params, cfg, 2 ** 17)
    assert params2["values"] is store
    assert store.num_ranges == 8 and cfg2.model_shards == 8
    assert all(p.writeback_lr == 0.25 for p in store.parts)
    after = store.to_dense()
    np.testing.assert_array_equal(after[:2 ** 16], before)
    np.testing.assert_array_equal(after[2 ** 16:], before)  # alias copy


def test_grow_model_with_opt_state():
    """Model-level growth: every lram/values leaf grows (params + Adam
    moments, parent-copied), per-feature leaves stay, and the returned
    config re-resolves cleanly."""
    from repro import optim

    cfg = configs.get_smoke_config("lram-tiered")
    cfg = dataclasses.replace(
        cfg, lram=dataclasses.replace(cfg.lram, interp_impl="reference",
                                      tiered=None)
    )
    params, state = transformer.init(KEY, cfg)
    opt = optim.adam_init(params)
    n_old = cfg.lram.num_locations

    params2, cfg2, opt2 = memctl.grow_model(params, cfg, 2 * n_old,
                                            opt_state=opt)
    assert cfg2.lram.num_locations == 2 * n_old
    vals = [leaf for path, leaf
            in jax.tree_util.tree_flatten_with_path(params2)[0]
            if "values" in str(path)]
    assert vals and all(v.shape[0] == 2 * n_old for v in vals)
    mus = [leaf for path, leaf
           in jax.tree_util.tree_flatten_with_path(opt2["mu"])[0]
           if "values" in str(path)]
    assert mus and all(m.shape[0] == 2 * n_old for m in mus)
    # logits at pre-growth points: the grown model must still run
    toks = {"tokens": jnp.zeros((2, 8), jnp.int32)}
    logits, _, _ = transformer.forward(params2, state, toks, cfg2)
    assert np.isfinite(np.asarray(logits)).all()


# ---------------------------------------------------------------------------
# migration: dense <-> tiered <-> sharded-tiered, any storage pair
# ---------------------------------------------------------------------------

def test_migration_roundtrip_exact_model_logits():
    """Acceptance: dense -> tiered -> sharded-tiered -> dense reproduces
    logits exactly (fp32 payload moves, never re-encoded)."""
    cfg_d = dataclasses.replace(
        configs.get_smoke_config("lram-tiered"),
        lram=dataclasses.replace(
            configs.get_smoke_config("lram-tiered").lram,
            interp_impl="reference", tiered=None,
        ),
    )
    params, state = transformer.init(KEY, cfg_d)
    toks = {"tokens": jnp.asarray(
        np.random.default_rng(0).integers(0, cfg_d.vocab_size, (2, 8)),
        jnp.int32)}
    y0 = np.asarray(transformer.forward(params, state, toks, cfg_d)[0])

    lram_t = dataclasses.replace(
        cfg_d.lram, interp_impl="tiered",
        tiered=TieredSpec(shard_rows=2048, cache_slots=4),
    )
    params, cfg_t = memctl.migrate_model(params, cfg_d, lram_t)
    y1 = np.asarray(transformer.forward(params, state, toks, cfg_t)[0])
    np.testing.assert_allclose(y1, y0, atol=1e-5)

    lram_st = dataclasses.replace(
        cfg_d.lram, interp_impl="sharded-tiered", model_shards=2,
        tiered=TieredSpec(shard_rows=2048, cache_slots=2),
    )
    params, cfg_st = memctl.migrate_model(params, cfg_t, lram_st)
    y2 = np.asarray(transformer.forward(params, state, toks, cfg_st)[0])
    np.testing.assert_allclose(y2, y0, atol=1e-5)

    params, cfg_back = memctl.migrate_model(params, cfg_st, cfg_d.lram)
    y3 = np.asarray(transformer.forward(params, state, toks, cfg_back)[0])
    np.testing.assert_array_equal(y3, y0)


def test_migration_same_kind_quant_payload_exact():
    """int8 -> int8 across placements moves payload + scales verbatim —
    no requantization drift, bit-equal dequantized tables."""
    cfg_dq = make_cfg("dense", "int8")
    params, _ = lram.lram_init(KEY, cfg_dq)
    table = params["values"]
    assert isinstance(table, quant.QuantizedTable)
    cfg_tq = make_cfg("tiered", "int8")
    p_t = memctl.migrate(params, cfg_dq, cfg_tq)
    store = p_t["values"]
    np.testing.assert_array_equal(
        store.to_dense(), np.asarray(table.dequantize())
    )
    # and back: payload survives a full cycle bit-exact
    p_d = memctl.migrate(p_t, cfg_tq, cfg_dq)
    np.testing.assert_array_equal(np.asarray(p_d["values"].q),
                                  np.asarray(table.q))
    np.testing.assert_array_equal(np.asarray(p_d["values"].scale),
                                  np.asarray(table.scale))


def test_migration_cross_storage_within_bound(rng):
    cfg_d = make_cfg("dense", "fp32")
    params, _ = lram.lram_init(KEY, cfg_d)
    dense = np.asarray(params["values"])
    cfg_q = make_cfg("sharded-tiered", "int8", model_shards=2)
    p_q = memctl.migrate(params, cfg_d, cfg_q)
    got = p_q["values"].to_dense()
    _, scale = quant.quantize_rows_np(dense, "int8")
    assert np.abs(got - dense).max() <= float(scale.max()) * 0.5 + 1e-7


def test_migration_rejects_mesh_and_resize():
    cfg = make_cfg("dense", "fp32")
    params, _ = lram.lram_init(KEY, cfg)
    mesh = make_mesh((1,), ("model",))
    _ctx.set_mesh(mesh)
    try:
        with pytest.raises(lookup.LookupPlanError, match="migrate"):
            memctl.migrate(params, cfg,
                           lram.LRAMConfig(**KW, interp_impl="sharded"))
    finally:
        _ctx.set_mesh(None)
    with pytest.raises(ValueError, match="shape"):
        memctl.migrate(params, cfg,
                       make_cfg("tiered", "fp32", log2_locations=17))


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

def test_telemetry_update_is_jit_safe_segment_sum(rng):
    tel = memctl.telemetry_init(1024, rows_per_bin=4)
    idx = rng.integers(0, 1024, size=(7, 5)).astype(np.int32)
    tel = jax.jit(memctl.telemetry_update)(tel, jnp.asarray(idx))
    counts = np.asarray(tel["counts"])
    want = np.bincount(idx.reshape(-1) // 4, minlength=256)
    np.testing.assert_array_equal(counts, want.astype(np.float32))
    assert int(tel["steps"]) == 1
    # second step decays the EMA toward the new hit vector
    tel2 = memctl.telemetry_update(tel, jnp.asarray(idx[:1]))
    assert float(np.asarray(tel2["ema"]).sum()) < float(counts.sum())


def test_utilisation_report_fractions():
    tel = memctl.telemetry_init(100, rows_per_bin=1)
    tel = memctl.telemetry_update(
        tel, jnp.asarray(np.arange(50, dtype=np.int32))
    )
    rows = memctl.utilisation_report(tel, prefix="t")
    byname = {r[0]: r[2] for r in rows}
    assert byname["t_dead_frac"].startswith("0.5000")
    from benchmarks.run import validate_summary

    validate_summary({"rows": rows})  # bench row schema


def test_store_telemetry_counts_accesses(rng):
    dense = rng.normal(size=(4096, 8)).astype(np.float32)
    store = ShardedTieredStore.from_dense(
        dense, TieredSpec(shard_rows=256, cache_slots=2), num_ranges=2
    )
    idx = rng.integers(0, 4096, size=(32, 4)).astype(np.int32)
    store.gather(idx, rng.normal(size=idx.shape).astype(np.float32))
    tel = memctl.store_telemetry(store)
    counts = np.asarray(tel["counts"])
    assert counts.shape == (16,) and int(tel["rows_per_bin"]) == 256
    want = np.bincount(idx.reshape(-1) >> 8, minlength=16)
    np.testing.assert_array_equal(counts, want.astype(np.float32))
    plan = lookup.resolve(make_cfg("sharded-tiered", "fp32"))
    assert plan.row_stats


def test_grow_telemetry_appends_dead_bins():
    tel = memctl.telemetry_init(512, rows_per_bin=8)
    tel = memctl.telemetry_update(
        tel, jnp.asarray(np.arange(512, dtype=np.int32))
    )
    tel2 = memctl.grow_telemetry(tel, 1024)
    counts = np.asarray(tel2["counts"])
    assert counts.shape == (128,)
    assert (counts[64:] == 0).all() and (counts[:64] > 0).all()


# ---------------------------------------------------------------------------
# the controller: train-step schedule and serve-tick spill
# ---------------------------------------------------------------------------

def test_parse_grow_at():
    assert memctl.parse_grow_at("10:17,20:18") == ((10, 17), (20, 18))
    with pytest.raises(ValueError, match="STEP:NEW_LOG2"):
        memctl.parse_grow_at("10")
    with pytest.raises(ValueError, match="increase"):
        memctl.parse_grow_at("10:18,20:17")
    with pytest.raises(ValueError, match="distinct"):
        memctl.parse_grow_at("10:17,10:18")


def test_controller_grows_on_schedule_once():
    cfg = configs.get_smoke_config("lram-tiered")
    params, _ = transformer.init(KEY, cfg)
    ctl = memctl.MemoryController(memctl.LifecyclePolicy(
        grow_at=memctl.parse_grow_at("2:17")
    ))
    n0 = cfg.lram.num_locations
    params, cfg, _, changed = ctl.on_train_step(0, params, cfg)
    assert not changed and cfg.lram.num_locations == n0
    params, cfg, _, changed = ctl.on_train_step(2, params, cfg)
    assert changed and cfg.lram.num_locations == 2 ** 17
    params, cfg, _, changed = ctl.on_train_step(2, params, cfg)
    assert not changed  # fires exactly once
    assert ctl.events and ctl.events[0]["event"] == "grow"


def test_controller_catch_up_applies_past_growths():
    cfg = configs.get_smoke_config("lram-tiered")
    params, _ = transformer.init(KEY, cfg)
    ctl = memctl.MemoryController(memctl.LifecyclePolicy(
        grow_at=memctl.parse_grow_at("1:17,5:18")
    ))
    params, cfg, _, changed = ctl.catch_up(3, params, cfg)
    assert changed and cfg.lram.num_locations == 2 ** 17  # only step-1 event


def test_engine_live_spill_preserves_generation():
    """The serve-tick spill (dense -> tiered mid-trace) must not change a
    single generated token: fp32 payload moves exactly and in-flight
    slots ride through the swap."""
    from repro.serving import EngineConfig, ServeEngine, synthetic_trace

    cfg = configs.get_smoke_config("lram-tiered")
    cfg = dataclasses.replace(
        cfg, lram=dataclasses.replace(cfg.lram, interp_impl="reference",
                                      tiered=None)
    )
    params, state = transformer.init(KEY, cfg)
    trace = synthetic_trace(np.random.default_rng(0), 4,
                            vocab_size=cfg.vocab_size, max_prompt=6,
                            max_gen=6)
    base = ServeEngine(params, state, cfg, EngineConfig(slots=2, max_len=16))
    want = {r.id: r.tokens for r in base.run(trace).requests}

    ctl = memctl.MemoryController(memctl.LifecyclePolicy(spill_at_tick=2))
    engine = ServeEngine(params, state, cfg,
                         EngineConfig(slots=2, max_len=16), controller=ctl)
    report = engine.run(trace)
    assert ctl.events and ctl.events[0]["event"] == "spill"
    assert engine.cfg.lram.interp_impl == "tiered"
    assert engine.stores  # prefetch handles discovered post-swap
    got = {r.id: r.tokens for r in report.requests}
    assert got == want


def test_controller_hbm_budget_trigger():
    cfg = configs.get_smoke_config("lram-tiered")
    cfg = dataclasses.replace(
        cfg, lram=dataclasses.replace(cfg.lram, interp_impl="reference",
                                      tiered=None)
    )
    table_bytes = cfg.lram.num_locations * cfg.lram.table_bytes_per_entry
    ctl = memctl.MemoryController(memctl.LifecyclePolicy(
        hbm_budget_bytes=table_bytes - 1
    ))

    class _Eng:  # the controller only reads cfg + ticks
        pass

    eng = _Eng()
    eng.cfg = cfg
    eng.ticks = 0
    assert ctl._spill_due(eng)
    ctl2 = memctl.MemoryController(memctl.LifecyclePolicy(
        hbm_budget_bytes=table_bytes + 1
    ))
    assert not ctl2._spill_due(eng)


# ---------------------------------------------------------------------------
# satellites: prefetch executor, plan-driven sharding rules
# ---------------------------------------------------------------------------

def test_sharded_tiered_prefetch_pool_matches_serial(rng):
    """The thread-pool prefetch warms exactly the shards the serial walk
    warmed, with identical fill/stat counting."""
    dense = rng.normal(size=(4096, 8)).astype(np.float32)
    spec = TieredSpec(shard_rows=256, cache_slots=2)
    a = ShardedTieredStore.from_dense(dense, spec, num_ranges=4)
    b = ShardedTieredStore.from_dense(dense, spec, num_ranges=4)
    idx = rng.integers(0, 4096, size=(64,)).astype(np.int32)
    for s in (a, b):
        s.gather_rows_host(idx)  # primes last_access per range
    a.prefetch_last()
    for part in b.parts:  # the old serial walk
        part.prefetch_last()
    assert a.resident_shards() == b.resident_shards()
    assert a.stats == b.stats
    a.prefetch(idx)  # the indexed variant fans out too
    assert a._pool is not None


def test_param_pspecs_plan_driven_memory_tables():
    """The resolved plan emits the memory table's pspec: replicated for
    dense placements, rows over `model` for the sharded placement — the
    regex rule for lram values is gone."""
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg_dense = configs.get_smoke_config("lram-tiered")
    cfg_dense = dataclasses.replace(
        cfg_dense, lram=dataclasses.replace(cfg_dense.lram,
                                            interp_impl="reference",
                                            tiered=None)
    )
    params, _ = transformer.init(KEY, cfg_dense)
    specs = sharding.param_pspecs(params, mesh, model_cfg=cfg_dense)
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    vals = [s for path, s in flat if "values" in str(path)]
    assert vals and all(s == P() for s in vals)

    _ctx.set_mesh(mesh)
    try:
        cfg_sh = dataclasses.replace(
            cfg_dense, lram=dataclasses.replace(cfg_dense.lram,
                                                interp_impl="sharded")
        )
        params_sh, _ = transformer.init(KEY, cfg_sh)
        specs_sh = sharding.param_pspecs(params_sh, mesh, model_cfg=cfg_sh)
    finally:
        _ctx.set_mesh(None)
    flat_sh = jax.tree_util.tree_flatten_with_path(specs_sh)[0]
    vals_sh = [s for path, s in flat_sh if "values" in str(path)]
    assert vals_sh and all(s == P("model", None) for s in vals_sh)
    # no regex rule for lram values remains
    import re

    from repro.distributed.sharding import _rules, MeshAxes

    for pat, _spec in _rules(MeshAxes()):
        assert not re.search(pat, "x/memffn/lram/values") or pat == r".*", pat


# ---------------------------------------------------------------------------
# e2e: train with a mid-run growth step, resume, then serve the checkpoint
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_train_grow_resume_serve_e2e(tmp_path):
    """Acceptance: an lram-tiered model trained with one mid-run --grow-at
    growth step trains end to end, a relaunch catches up past growths
    before restoring (grow-on-restore shapes line up), and the grown
    checkpoint serves via --grow-to."""
    import textwrap

    from conftest import run_in_subprocess

    ckpt = str(tmp_path / "ckpt")
    out = run_in_subprocess(textwrap.dedent(f"""
        from repro.launch import train
        train.main(["--arch", "lram-tiered", "--smoke", "--steps", "4",
                    "--batch", "2", "--seq", "16", "--grow-at", "2:17",
                    "--ckpt-dir", {ckpt!r}, "--ckpt-every", "2",
                    "--log-every", "1"])
    """), timeout=900)
    assert '"grow": "2^17"' in out

    # relaunch: catch_up re-applies the growth, restore resumes at step 4
    out2 = run_in_subprocess(textwrap.dedent(f"""
        from repro.launch import train
        train.main(["--arch", "lram-tiered", "--smoke", "--steps", "6",
                    "--batch", "2", "--seq", "16", "--grow-at", "2:17",
                    "--ckpt-dir", {ckpt!r}, "--ckpt-every", "100",
                    "--log-every", "1"])
    """), timeout=900)
    assert "resumed from step 4" in out2

    out3 = run_in_subprocess(textwrap.dedent(f"""
        from repro.launch import serve
        serve.main(["--arch", "lram-tiered", "--smoke", "--batch", "2",
                    "--prompt-len", "4", "--gen", "3", "--grow-to", "17",
                    "--ckpt-dir", {ckpt!r}, "--json"])
    """), timeout=900)
    assert '"restored_step"' in out3 and '"tokens_per_sec"' in out3
