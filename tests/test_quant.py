"""Quantized value tables: round-trip error bounds, agreement of every
lookup implementation (reference | pallas | tiered | sharded) with the fp32
reference under jit and grad, unbiasedness of the stochastic-rounding
write-back, and quantized checkpoint save/restore."""

import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_in_subprocess
from repro import quant
from repro.checkpoint import CheckpointManager
from repro.core import lram
from repro.memstore import TieredSpec, TieredValueStore

KEY = jax.random.PRNGKey(0)
KINDS = ("int8", "fp8")


# ---------------------------------------------------------------------------
# codec round trip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", KINDS)
def test_round_trip_error_bound(rng, kind):
    """Nearest rounding stays within half a grid step of the fp32 row:
    scale/2 for int8's uniform grid, |v| * 2**-4 for fp8 (e4m3)."""
    v = rng.normal(size=(256, 16)).astype(np.float32) * 0.02
    q, scale = quant.quantize_rows_np(v, kind)
    assert q.dtype == quant.storage_dtype(kind) and q.dtype.itemsize == 1
    back = quant.dequantize_rows_np(q, scale)
    if kind == "int8":
        bound = scale[:, None] / 2 + 1e-7
    else:
        bound = np.abs(v) * 2.0**-4 + scale[:, None] + 1e-7
    assert np.all(np.abs(back - v) <= bound)


def test_bytes_per_entry():
    assert quant.bytes_per_entry(64, None) == 256
    assert quant.bytes_per_entry(64, "int8") == 68
    assert quant.bytes_per_entry(64, "fp8") == 68
    assert 256 / 68 >= 3.5  # the acceptance floor


# ---------------------------------------------------------------------------
# all four lookup implementations vs the fp32 reference
# ---------------------------------------------------------------------------

def _quant_cfg(kind, **kw):
    base = dict(log2_locations=16, m=8, heads=4, query_norm="rms")
    base.update(kw)
    return lram.LRAMConfig(table_quant=kind, **base)


@pytest.mark.parametrize("kind", KINDS)
def test_interp_error_vs_fp32_within_documented_bound(rng, kind):
    """The documented tolerance: a quantized gather+interpolate differs from
    the fp32 one by at most repro.quant.max_abs_error_bound."""
    values = rng.normal(size=(2**16, 8)).astype(np.float32) * 0.02
    qt = quant.QuantizedTable.from_dense(values, kind)
    idx = jnp.asarray(rng.integers(0, 2**16, size=(64, 32)))
    w = jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32))
    out_fp = lram.gather_interp(jnp.asarray(values), idx, w)
    out_q = quant.gather_interp_quant(qt, idx, w)
    bound = quant.max_abs_error_bound(np.asarray(qt.scale),
                                      np.asarray(w), kind)
    assert np.abs(np.asarray(out_q) - np.asarray(out_fp)).max() \
        <= bound + 1e-6


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("impl", ["reference", "pallas", "tiered"])
def test_quantized_layer_impls_agree(kind, impl):
    """Every in-process impl of the quantized layer produces the same
    output as the quantized-twin reference (same init rounding), eager +
    jit + grad-of-input; and tracks the fp32 layer closely."""
    kw = {}
    if impl == "tiered":
        kw = dict(
            interp_impl="tiered",
            tiered=TieredSpec(shard_rows=4096, cache_slots=4),  # <50% resident
        )
    cfg_fp = lram.LRAMConfig(log2_locations=16, m=8, heads=4,
                             query_norm="rms")
    cfg_q = _quant_cfg(kind, **kw)
    cfg_qref = _quant_cfg(kind)
    p_fp, s_fp = lram.lram_init(KEY, cfg_fp)
    p_q, s_q = lram.lram_init(KEY, cfg_q)
    p_r, s_r = lram.lram_init(KEY, cfg_qref)
    x = jax.random.normal(KEY, (3, 5, cfg_fp.in_dim))

    y_fp, _ = lram.lram_apply(p_fp, s_fp, x, cfg_fp)
    y_ref, _ = lram.lram_apply(p_r, s_r, x, cfg_qref)  # quantized reference
    impl_arg = None if impl == "tiered" else impl
    y_q, _ = lram.lram_apply(p_q, s_q, x, cfg_q, interp_impl=impl_arg)
    np.testing.assert_allclose(np.asarray(y_q), np.asarray(y_ref), atol=1e-5)
    # sanity vs the fp32 twin: rounding noise only (the hard bound is
    # asserted at interp level in test_interp_error_vs_fp32_*)
    np.testing.assert_allclose(np.asarray(y_q), np.asarray(y_fp),
                               atol=2e-2, rtol=2e-2)

    y_j = jax.jit(
        lambda xx: lram.lram_apply(p_q, s_q, xx, cfg_q,
                                   interp_impl=impl_arg)[0]
    )(x)
    np.testing.assert_allclose(np.asarray(y_j), np.asarray(y_ref), atol=1e-5)

    g_ref = jax.grad(
        lambda xx: jnp.sum(lram.lram_apply(p_r, s_r, xx, cfg_qref)[0] ** 2)
    )(x)
    g_q = jax.grad(
        lambda xx: jnp.sum(
            lram.lram_apply(p_q, s_q, xx, cfg_q, interp_impl=impl_arg)[0] ** 2
        )
    )(x)
    np.testing.assert_allclose(np.asarray(g_q), np.asarray(g_ref),
                               atol=1e-4, rtol=1e-4)
    assert bool(jnp.isfinite(g_q).all())


@pytest.mark.slow
def test_quantized_sharded_lookup_matches_reference():
    """impl #4: the model-parallel shard_map lookup dequantizes shard-local
    rows and psums fp32 partials — same bound, jit + grad, 8 fake devices."""
    run_in_subprocess(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro import quant
        from repro.core import indexing, lram
        from repro.distributed.sharded_lram import sharded_gather_interp

        mesh = make_mesh((2, 4), ("data", "model"))
        spec = indexing.choose_torus(16)
        rng = np.random.default_rng(0)
        values = rng.normal(size=(spec.num_locations, 16)) \\
            .astype(np.float32) * 0.02
        q = jnp.asarray(rng.uniform(0, 8, size=(8, 3, 8)).astype(np.float32))
        idx, w = lram.indices_and_weights(q, spec, 32)
        qt = quant.QuantizedTable.from_dense(values, "int8")
        interp = sharded_gather_interp(mesh, axis="model")

        got = interp(qt, idx, w)
        want_q = quant.gather_interp_quant(qt, idx, w)  # quantized reference
        np.testing.assert_allclose(np.asarray(got), np.asarray(want_q),
                                   rtol=1e-5, atol=1e-5)
        want_fp = jnp.einsum("...k,...km->...m", w,
                             jnp.asarray(values)[idx])
        bound = quant.max_abs_error_bound(
            np.asarray(qt.scale), np.asarray(w), "int8") + 1e-6
        assert np.abs(np.asarray(got) - np.asarray(want_fp)).max() <= bound

        jitted = jax.jit(lambda i, ww: interp(qt, i, ww))
        np.testing.assert_allclose(np.asarray(jitted(idx, w)),
                                   np.asarray(want_q), rtol=1e-5, atol=1e-5)
        g = jax.grad(lambda ww: jnp.sum(interp(qt, idx, ww) ** 2))(w)
        g_ref = jax.grad(
            lambda ww: jnp.sum(quant.gather_interp_quant(qt, idx, ww) ** 2)
        )(w)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   rtol=1e-4, atol=1e-4)
        print("sharded quantized lram OK")
    """), devices=8)


@pytest.mark.parametrize("kind", KINDS)
def test_tiered_quant_gather_matches_quantized_twin(rng, kind):
    """All tiered serving paths (eager cache gather, overflow, pallas
    indirected kernel, traced io_callback) reproduce the dense quantized
    table bit-for-bit (same rounding at init)."""
    dense = rng.normal(size=(4096, 16)).astype(np.float32) * 0.02
    deq = np.asarray(quant.QuantizedTable.from_dense(dense, kind).dequantize())
    idx = rng.integers(0, 4096, size=(8, 32)).astype(np.int32)
    w = rng.normal(size=idx.shape).astype(np.float32)
    want = np.einsum("...k,...km->...m", w, deq[idx])
    for use_pallas in (False, True):
        store = TieredValueStore.from_dense(
            dense, TieredSpec(shard_rows=256, cache_slots=4, quant=kind,
                              use_pallas=use_pallas)
        )
        out = np.asarray(store.gather(idx, w))  # overflow: 4 slots, 16 shards
        np.testing.assert_allclose(out, want, atol=1e-5)
        assert store.stats["uncached"] > 0
        from repro import memstore
        out_j = jax.jit(
            lambda i, ww: memstore.tiered_interp(store, i, ww)
        )(jnp.asarray(idx), jnp.asarray(w))
        np.testing.assert_allclose(np.asarray(out_j), want, atol=1e-5)


def test_quantized_fill_bytes_shrink(rng):
    """The host->device fill traffic for an int8 store is ~4x smaller than
    its fp32 twin — the PCIe half of the quantization win."""
    dense = rng.normal(size=(4096, 64)).astype(np.float32)
    counts = {}
    for quant_kind in ("none", "int8"):
        store = TieredValueStore.from_dense(
            dense, TieredSpec(shard_rows=256, cache_slots=4, quant=quant_kind)
        )
        idx = rng.integers(0, 1024, size=(8, 32)).astype(np.int32)
        store.gather(idx, rng.normal(size=idx.shape).astype(np.float32))
        counts[quant_kind] = store.stats["fill_bytes"]
    assert counts["none"] >= 3.5 * counts["int8"]


# ---------------------------------------------------------------------------
# stochastic rounding + write-back
# ---------------------------------------------------------------------------

def test_stochastic_rounding_unbiased():
    """E[quantize_sr(v)] == v: averaged over seeds, stochastic rounding has
    no systematic drift (nearest rounding would bias every draw the same
    way)."""
    v = np.linspace(-0.9, 0.9, 16, dtype=np.float32)[None, :] * 0.013
    draws = []
    for seed in range(400):
        q, s = quant.quantize_rows_np(v, "int8",
                                      rng=np.random.default_rng(seed))
        draws.append(quant.dequantize_rows_np(q, s))
    mean = np.mean(draws, axis=0)
    step = np.abs(v).max() / 127.0  # one quantization step
    # CLT: sd of the mean <= step / sqrt(12 * 400) ~= step / 69
    assert np.abs(mean - v).max() < 0.15 * step
    # while a single nearest-rounded draw is off by up to step/2
    q, s = quant.quantize_rows_np(v, "int8")
    assert np.abs(quant.dequantize_rows_np(q, s) - v).max() <= step / 2 + 1e-9


def test_quantized_writeback_applies_expected_update(rng):
    """dequant(after) ~= dequant(before) - lr * wg on touched rows, within
    one (stochastic) quantization step; untouched rows bit-identical."""
    dense = rng.normal(size=(2048, 8)).astype(np.float32) * 0.02
    store = TieredValueStore.from_dense(
        dense, TieredSpec(shard_rows=256, cache_slots=4, quant="int8")
    )
    store.writeback_lr = 0.5
    before = store.to_dense()
    idx = rng.integers(0, 2048, size=(64,)).astype(np.int32)
    wg = rng.normal(size=(64, 8)).astype(np.float32) * 0.01
    store.gather_rows_host(idx)  # makes some shards resident
    store.apply_writeback(idx, wg)
    assert store._dirty, "resident rows must mark their slots dirty"
    after = store.to_dense()

    expected = before.copy()
    np.add.at(expected, idx, -0.5 * wg)  # duplicates accumulate
    touched = np.zeros(2048, bool)
    touched[idx] = True
    np.testing.assert_array_equal(after[~touched], before[~touched])
    # requantization error: one step of the fresh per-row scale
    scale = np.abs(expected[touched]).max(axis=-1) / 127.0
    assert np.all(
        np.abs(after[touched] - expected[touched]) <= scale[:, None] + 1e-7
    )


def test_quantized_writeback_unbiased_in_expectation(rng):
    """The same sub-quantum update applied across many rng seeds moves the
    mean stored value by ~the true update (nearest rounding would leave a
    small update invisible forever)."""
    row = (rng.normal(size=(1, 8)) * 0.02).astype(np.float32)
    upd = np.full((1, 8), 1e-5, np.float32)  # << one quantization step
    step = np.abs(row).max() / 127.0
    assert upd[0, 0] < step / 4
    before = quant.dequantize_rows_np(*quant.quantize_rows_np(row, "int8"))[0]
    deltas = []
    for seed in range(300):
        store = TieredValueStore.from_dense(
            np.repeat(row, 256, axis=0),
            TieredSpec(shard_rows=256, cache_slots=1, quant="int8"),
        )
        store.writeback_lr = 1.0
        store._wb_rng = np.random.default_rng(seed)
        store.gather_rows_host(np.zeros((1,), np.int32))
        store.apply_writeback(np.zeros((1,), np.int32), -upd)  # SGD: -= -upd
        deltas.append(store.to_dense()[0] - before)
    mean_delta = np.mean(deltas, axis=0)
    np.testing.assert_allclose(mean_delta, upd[0], atol=step / 8)


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_quantized_dirty_checkpoint_round_trip(rng, tmp_path):
    """Quantized store with dirty shards: save streams payload + scales;
    restore into a fresh quantized store is bit-exact; restore into a dense
    proto and a dense checkpoint into a quantized store both convert."""
    dense = rng.normal(size=(2048, 8)).astype(np.float32) * 0.02
    spec = TieredSpec(shard_rows=256, cache_slots=3, quant="int8")
    store = TieredValueStore.from_dense(dense, spec)
    store.writeback_lr = 0.5
    idx = rng.integers(0, 2048, size=(64,)).astype(np.int32)
    store.gather_rows_host(idx)
    store.apply_writeback(idx, rng.normal(size=(64, 8)).astype(np.float32))
    assert store._dirty, "test needs dirty cached shards"

    mgr = CheckpointManager(str(tmp_path))
    mgr.save(3, {"values": store})
    expected = store.to_dense()

    import json
    import os
    man = json.load(open(os.path.join(
        str(tmp_path), "step_000000000003", "manifest.json")))
    meta = man["leaves"]["values"]
    assert meta["quant"] == "int8"
    assert len(meta["scale_crc32"]) == store.num_shards
    assert os.path.exists(os.path.join(
        str(tmp_path), "step_000000000003", meta["dir"], "scale_000000.npy"))

    fresh = TieredValueStore(2048, 8, spec)
    step, _ = mgr.restore({"values": fresh})
    assert step == 3
    np.testing.assert_array_equal(fresh.to_dense(), expected)
    np.testing.assert_array_equal(np.asarray(fresh._host),
                                  np.asarray(store._host))

    # quantized checkpoint -> dense proto (dequantized host-side)
    _, r = mgr.restore({"values": jnp.zeros((2048, 8))})
    np.testing.assert_allclose(np.asarray(r["values"]), expected, atol=1e-7)

    # quantized checkpoint -> unquantized tiered store (dequant per shard)
    dense_store = TieredValueStore(
        2048, 8, TieredSpec(shard_rows=256, cache_slots=3)
    )
    mgr.restore({"values": dense_store})
    np.testing.assert_allclose(dense_store.to_dense(), expected, atol=1e-7)

    # dense checkpoint -> quantized store (requantized per shard, nearest)
    mgr2 = CheckpointManager(str(tmp_path / "dense"))
    dense_store.flush()
    mgr2.save(1, {"values": dense_store})
    q_store = TieredValueStore(2048, 8, spec)
    mgr2.restore({"values": q_store})
    q_ref, s_ref = quant.quantize_rows_np(expected, "int8")
    np.testing.assert_array_equal(
        np.asarray(q_store._host).reshape(2048, 8), q_ref
    )


def test_corrupt_scale_falls_back(rng, tmp_path):
    """A corrupt scale file is caught by its own checksum and triggers the
    same newest-first fallback as a corrupt payload shard."""
    dense = rng.normal(size=(1024, 8)).astype(np.float32)
    spec = TieredSpec(shard_rows=128, cache_slots=2, quant="int8")
    store = TieredValueStore.from_dense(dense, spec)
    mgr = CheckpointManager(str(tmp_path), keep=5)
    mgr.save(1, {"values": store})
    expected = store.to_dense()
    store.writeback_lr = 0.5
    idx = rng.integers(0, 1024, size=(32,)).astype(np.int32)
    store.gather_rows_host(idx)
    store.apply_writeback(idx, rng.normal(size=(32, 8)).astype(np.float32))
    mgr.save(2, {"values": store})

    import os
    bad = os.path.join(str(tmp_path), "step_000000000002",
                       "values.npy.shards", "scale_000002.npy")
    with open(bad, "wb") as f:
        f.write(b"garbage")
    fresh = TieredValueStore(1024, 8, spec)
    step, _ = mgr.restore({"values": fresh})
    assert step == 1
    np.testing.assert_array_equal(fresh.to_dense(), expected)


# ---------------------------------------------------------------------------
# config plumbing
# ---------------------------------------------------------------------------

def test_lram_tiered_q8_config_registered():
    from repro import configs
    cfg = configs.get_smoke_config("lram-tiered-q8")
    assert cfg.lram.table_quant == "int8"
    assert cfg.lram.tiered.quant == "int8"
    assert cfg.lram.table_bytes_per_entry == 68
    # quantized cache budget: same slots hold ~4x less memory
    params, _ = lram.lram_init(KEY, cfg.lram)
    store = params["values"]
    assert store.quant == "int8"
    assert store.cache_np.dtype.itemsize == 1


def test_table_quant_validation():
    with pytest.raises(ValueError):
        lram.LRAMConfig(table_quant="int4")
    with pytest.raises(ValueError):
        TieredSpec(quant="bogus")
