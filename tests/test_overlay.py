"""Per-tenant memory overlays (`repro.serving.overlay` + the
`repro.core.overlay` pack protocol): overlay semantics property-tested
against pure-dict reference models under random op interleavings, tenant
isolation on the serve engine (empty overlay == no overlay, bit-exact;
mixed-tenant == each tenant alone), lifecycle enforcement that never
perturbs in-flight requests, spill/restore round trips, and the
zero-recompilation attach/detach guarantee."""

import collections
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypo import given, settings, st
from repro import configs, memctl, quant
from repro.core import lookup, lram
from repro.launch.mesh import make_mesh
from repro.memstore import TieredSpec, TieredValueStore
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.serving import (
    EngineConfig,
    OverlayManager,
    Request,
    ServeEngine,
    TenantOverlay,
    synthetic_trace,
)

KEY = jax.random.PRNGKey(0)
KW = dict(log2_locations=16, m=8, heads=2, query_norm="rms")
STORAGES = ("fp32", "int8", "fp8")


def _roundtrip(v, storage):
    """What one overlay write stores: the base table's storage grid."""
    v = np.asarray(v, np.float32)
    if storage == "fp32":
        return v.copy()
    q, scale = quant.quantize_rows_np(v, storage)
    return quant.dequantize_rows_np(
        q[None], np.asarray([scale], np.float32)
    )[0]


def _row(seed, m=4):
    return np.random.default_rng(seed).normal(size=m).astype(np.float32)


# ---------------------------------------------------------------------------
# property: TenantOverlay == an OrderedDict reference model
# ---------------------------------------------------------------------------

class RefOverlay:
    """Pure-dict reference: per-layer row -> effective fp32 value, with
    insertion-order recency and evict-oldest beyond capacity."""

    def __init__(self, num_layers, m, storage, cap):
        self.m, self.storage, self.cap = m, storage, cap
        self.rows = [collections.OrderedDict() for _ in range(num_layers)]

    def write(self, layer, row, v):
        od = self.rows[layer]
        od.pop(row, None)
        od[row] = _roundtrip(v, self.storage)
        while len(od) > self.cap:
            od.popitem(last=False)

    def read(self, layer, row):
        return self.rows[layer].get(row)

    def evict(self, layer, row):
        return self.rows[layer].pop(row, None) is not None


def _assert_overlay_matches(ov: TenantOverlay, ref: RefOverlay):
    assert ov.num_rows == sum(len(od) for od in ref.rows)
    for layer, od in enumerate(ref.rows):
        assert ov.packed_rows(layer) == list(od), (
            f"layer {layer}: recency order diverged"
        )
        for row, want in od.items():
            got = ov.read(layer, row)
            np.testing.assert_array_equal(got, want)


@settings(max_examples=60, deadline=None)
@given(st.data())
def test_tenant_overlay_matches_reference_model(data):
    """Random write/read/evict interleavings: the overlay's visible state
    (reads, row count, recency order) equals the reference model exactly,
    for every storage kind."""
    storage = data.draw(st.sampled_from(STORAGES))
    cap = data.draw(st.integers(min_value=1, max_value=4))
    layers = data.draw(st.integers(min_value=1, max_value=2))
    ops = data.draw(st.lists(
        st.tuples(
            st.sampled_from(["write", "read", "evict"]),
            st.integers(min_value=0, max_value=1),   # layer (mod layers)
            st.integers(min_value=0, max_value=7),   # row id
            st.integers(min_value=0, max_value=999),  # value seed
        ),
        max_size=50,
    ))
    ov = TenantOverlay("t", num_layers=layers, m=4, storage=storage,
                       max_rows=cap)
    ref = RefOverlay(layers, 4, storage, cap)
    for op, layer, row, seed in ops:
        layer %= layers
        if op == "write":
            v = _row(seed)
            ov.write(layer, row, v)
            ref.write(layer, row, v)
        elif op == "read":
            got, want = ov.read(layer, row), ref.read(layer, row)
            assert (got is None) == (want is None)
            if want is not None:
                np.testing.assert_array_equal(got, want)
        else:
            assert ov.evict(layer, row) == ref.evict(layer, row)
        _assert_overlay_matches(ov, ref)


@pytest.mark.parametrize("storage", STORAGES)
def test_tenant_overlay_save_load_roundtrip(storage, tmp_path):
    """npz persistence is lossless in storage form (fp8 payloads ride as
    uint8 views; scales and recency order survive)."""
    rng = np.random.default_rng(3)
    ov = TenantOverlay("u/1", num_layers=2, m=4, storage=storage,
                       max_rows=8)
    for i in range(12):
        ov.write(int(rng.integers(0, 2)), int(rng.integers(0, 16)),
                 rng.normal(size=4).astype(np.float32))
    ov.last_used_tick = 7
    path = str(tmp_path / "ov.npz")
    ov.save(path)
    back = TenantOverlay.load(path, m=4)
    assert back.tenant_id == "u/1" and back.storage == storage
    assert back.last_used_tick == 7 and back.writes == ov.writes
    for layer in range(2):
        assert back.packed_rows(layer) == ov.packed_rows(layer)
        for row in ov.packed_rows(layer):
            np.testing.assert_array_equal(back.read(layer, row),
                                          ov.read(layer, row))


# ---------------------------------------------------------------------------
# property: OverlayManager == a reference model under op interleavings
# ---------------------------------------------------------------------------

class _RefManager:
    """Reference semantics for attach/detach/writeback/enforce, built on
    RefOverlay + plain loops (vs the manager's vectorized paths)."""

    def __init__(self, base, storage, slots, cap, lr, spill_dir):
        self.base = base                      # (L, N, m) fp32
        self.L, _, self.m = base.shape
        self.storage, self.cap, self.lr = storage, cap, lr
        self.spill_dir = spill_dir
        self.slot_tenant = [None] * slots
        self.overlays = {}
        self.spilled = {}                     # tenant -> parked RefOverlay
        self.last_used = {}

    def _get(self, tid):
        if tid not in self.overlays:
            self.overlays[tid] = RefOverlay(self.L, self.m, self.storage,
                                            self.cap)
            self.last_used.setdefault(tid, 0)
        ov = self.overlays[tid]
        parked = self.spilled.pop(tid, None)
        if parked is not None and not any(len(od) for od in ov.rows):
            self.overlays[tid] = ov = parked
        return ov

    def attach(self, slot, tid, tick):
        self.detach(slot)
        if tid is None:
            return
        self._get(tid)
        self.last_used[tid] = max(self.last_used[tid], tick)
        self.slot_tenant[slot] = tid

    def detach(self, slot):
        self.slot_tenant[slot] = None

    def effective(self, tid, layer, row):
        got = self.overlays[tid].read(layer, row)
        return self.base[layer][row] if got is None else got

    def writeback(self, slot, idx, w, y, tick):
        tid = self.slot_tenant[slot]
        if tid is None:
            return
        ov = self.overlays[tid]
        for layer in range(self.L):
            flat = idx[layer].reshape(-1)
            k = idx[layer].shape[-1]
            agg = {}
            for i, r in enumerate(flat.tolist()):
                contrib = (w[layer].reshape(-1)[i]
                           * y[layer][i // k]).astype(np.float32)
                agg[r] = agg.get(r, np.zeros(self.m, np.float32)) + contrib
            # the manager aggregates over np.unique's sorted row order
            for r in sorted(agg):
                ov.write(layer, r, self.effective(tid, layer, r)
                         + self.lr * agg[r])
        self.last_used[tid] = max(self.last_used[tid], tick)

    def nbytes(self, tid):
        kind = None if self.storage == "fp32" else self.storage
        return (sum(len(od) for od in self.overlays[tid].rows)
                * quant.bytes_per_entry(self.m, kind))

    def enforce(self, tick, ttl, budget):
        attached = {t for t in self.slot_tenant if t is not None}

        def offload(tid):
            if self.spill_dir is not None:
                self.spilled[tid] = self.overlays[tid]
            self.overlays[tid] = RefOverlay(self.L, self.m, self.storage,
                                            self.cap)

        if ttl is not None:
            for tid in list(self.overlays):
                if tid in attached or self.nbytes(tid) == 0:
                    continue
                if tick - self.last_used[tid] >= ttl:
                    offload(tid)
        if budget is not None:
            total = sum(self.nbytes(t) for t in self.overlays)
            if total > budget:
                lru = sorted((self.last_used[t], t) for t in self.overlays
                             if t not in attached and self.nbytes(t) > 0)
                for _, tid in lru:
                    if total <= budget:
                        break
                    total -= self.nbytes(tid)
                    offload(tid)


def _assert_manager_matches(mgr: OverlayManager, ref: _RefManager):
    assert mgr.slot_tenant == ref.slot_tenant
    assert set(mgr.overlays) == set(ref.overlays)
    for tid, rov in ref.overlays.items():
        _assert_overlay_matches(mgr.overlays[tid], rov)
    # pack invariant: detached slots are inert; attached slots carry
    # exactly the tenant's rows with delta = effective - base
    for b, tid in enumerate(mgr.slot_tenant):
        if tid is None:
            assert (mgr.ids[:, b] == -1).all()
            assert (mgr.deltas[:, b] == 0.0).all()
            continue
        for layer in range(ref.L):
            packed = list(ref.overlays[tid].rows[layer])
            n = len(packed)
            assert mgr.ids[layer, b, :n].tolist() == packed
            assert (mgr.ids[layer, b, n:] == -1).all()
            for j, r in enumerate(packed):
                np.testing.assert_array_equal(
                    mgr.deltas[layer, b, j],
                    ref.effective(tid, layer, r) - ref.base[layer][r],
                )


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_manager_matches_reference_under_interleavings(data):
    """Random attach/detach/writeback/enforce interleavings: tenant rows,
    recency, per-slot packs (delta = effective - base), and
    spill-restore-on-attach all match the reference model exactly."""
    storage = data.draw(st.sampled_from(STORAGES))
    spill = data.draw(st.booleans())
    L, m, slots, cap, N, heads, k = 2, 4, 2, 3, 16, 2, 2
    rng = np.random.default_rng(
        data.draw(st.integers(min_value=0, max_value=2**31))
    )
    base = rng.normal(size=(L, N, m)).astype(np.float32)
    with tempfile.TemporaryDirectory() as tmp:
        spill_dir = tmp if spill else None
        mgr = OverlayManager(num_layers=L, m=m, storage=storage,
                             slots=slots, rows=cap, write_lr=0.5,
                             spill_dir=spill_dir)
        mgr.set_base_reader(
            lambda layer, rows: base[layer][np.asarray(rows, np.int64)]
        )
        ref = _RefManager(base, storage, slots, cap, 0.5, spill_dir)
        tick = 0
        for _ in range(data.draw(st.integers(min_value=1, max_value=30))):
            op = data.draw(st.sampled_from(
                ["attach", "detach", "writeback", "enforce", "tick"]
            ))
            if op == "tick":
                tick += data.draw(st.integers(min_value=1, max_value=3))
            elif op == "attach":
                slot = data.draw(st.integers(min_value=0,
                                             max_value=slots - 1))
                tid = data.draw(st.sampled_from(["A", "B", "C", None]))
                mgr.attach(slot, tid, tick=tick)
                ref.attach(slot, tid, tick)
            elif op == "detach":
                slot = data.draw(st.integers(min_value=0,
                                             max_value=slots - 1))
                mgr.detach(slot)
                ref.detach(slot)
            elif op == "writeback":
                slot = data.draw(st.integers(min_value=0,
                                             max_value=slots - 1))
                seed = data.draw(st.integers(min_value=0, max_value=999))
                r2 = np.random.default_rng(seed)
                idx = r2.integers(0, N, size=(L, heads, k))
                w = r2.normal(size=(L, heads, k)).astype(np.float32)
                y = r2.normal(size=(L, heads, m)).astype(np.float32)
                mgr.writeback(slot, idx, w, y, tick=tick)
                ref.writeback(slot, idx, w, y, tick)
            else:
                ttl = data.draw(st.sampled_from([None, 1, 3]))
                budget = data.draw(st.sampled_from([None, 0, 64]))
                mgr.enforce(tick=tick, ttl_ticks=ttl, budget_bytes=budget)
                ref.enforce(tick, ttl, budget)
            _assert_manager_matches(mgr, ref)


def test_enforce_never_touches_attached_tenants(tmp_path):
    """TTL expiry and budget pressure only offload *detached* tenants —
    an in-flight request keeps its overlay no matter the policy."""
    base = np.zeros((1, 8, 4), np.float32)
    mgr = OverlayManager(num_layers=1, m=4, storage="fp32", slots=2,
                         rows=4, spill_dir=str(tmp_path))
    mgr.set_base_reader(lambda layer, rows: base[layer][rows])
    mgr.attach(0, "inflight", tick=0)
    for tid in ("inflight", "idle"):
        mgr.get(tid).write(0, 3, np.ones(4, np.float32))
    events = mgr.enforce(tick=100, ttl_ticks=1, budget_bytes=0)
    assert [e["tenant"] for e in events] == ["idle"]
    assert events[0]["action"] == "spill"
    assert mgr.get("inflight").num_rows == 1
    assert mgr.overlays["idle"].num_rows == 0
    # the spilled tenant restores transparently on its next attach
    mgr.attach(1, "idle", tick=101)
    assert mgr.stats["restores"] == 1
    np.testing.assert_array_equal(mgr.get("idle").read(0, 3),
                                  np.ones(4, np.float32))


def test_enforce_without_spill_dir_drops(tmp_path):
    mgr = OverlayManager(num_layers=1, m=4, storage="fp32", slots=1,
                         rows=4)
    mgr.set_base_reader(lambda layer, rows: np.zeros((len(rows), 4),
                                                     np.float32))
    mgr.get("gone").write(0, 1, np.ones(4, np.float32))
    events = mgr.enforce(tick=9, ttl_ticks=1)
    assert events[0]["action"] == "drop" and mgr.stats["drops"] == 1
    mgr.attach(0, "gone", tick=10)
    assert mgr.get("gone").num_rows == 0  # nothing to restore


def test_manager_save_all_load_all_roundtrip(tmp_path):
    mgr = OverlayManager(num_layers=2, m=4, storage="int8", slots=1,
                         rows=4)
    mgr.set_base_reader(lambda layer, rows: np.zeros((len(rows), 4),
                                                     np.float32))
    rng = np.random.default_rng(0)
    for tid in ("a", "b/c"):
        for i in range(3):
            mgr.get(tid).write(i % 2, i, rng.normal(size=4))
    assert mgr.save_all(str(tmp_path)) == 2
    back = OverlayManager(num_layers=2, m=4, storage="int8", slots=1,
                          rows=4)
    assert back.load_all(str(tmp_path)) == 2
    for tid in ("a", "b/c"):
        _want, _got = mgr.overlays[tid], back.overlays[tid]
        for layer in range(2):
            assert _got.packed_rows(layer) == _want.packed_rows(layer)
            for r in _want.packed_rows(layer):
                np.testing.assert_array_equal(_got.read(layer, r),
                                              _want.read(layer, r))
    wrong = OverlayManager(num_layers=2, m=4, storage="fp8", slots=1,
                           rows=4)
    with pytest.raises(ValueError, match="expects"):
        wrong.load_all(str(tmp_path))


# ---------------------------------------------------------------------------
# plan capability: overlay support composes with placement x storage
# ---------------------------------------------------------------------------

def test_supports_overlay_capability_matrix():
    assert lookup.resolve(lram.LRAMConfig(**KW)).supports_overlay
    assert lookup.resolve(
        lram.LRAMConfig(**KW, table_quant="int8")
    ).supports_overlay
    tiered = lram.LRAMConfig(
        **KW, interp_impl="tiered", table_quant="fp8",
        tiered=TieredSpec(shard_rows=4096, cache_slots=4),
    )
    assert lookup.resolve(tiered).supports_overlay
    shti = lram.LRAMConfig(
        **KW, interp_impl="sharded-tiered", model_shards=4,
        tiered=TieredSpec(shard_rows=2048, cache_slots=2),
    )
    assert lookup.resolve(shti).supports_overlay
    mesh = make_mesh((1,), ("model",))
    from repro.distributed import context as _ctx
    _ctx.set_mesh(mesh)
    try:
        sharded = lookup.resolve(lram.LRAMConfig(**KW,
                                                 interp_impl="sharded"))
    finally:
        _ctx.set_mesh(None)
    assert not sharded.supports_overlay  # mesh-resident rows: no host CoW


@pytest.mark.parametrize("storage", STORAGES)
def test_read_rows_fp32_matches_table_forms(storage, rng):
    """The base-row reader the overlay deltas are computed against agrees
    across the table's dense / quantized / tiered forms."""
    dense = rng.normal(size=(1024, 8)).astype(np.float32)
    rows = rng.integers(0, 1024, size=(16,))
    if storage == "fp32":
        want = dense[rows]
        got_dense = lookup.read_rows_fp32(jnp.asarray(dense), rows)
        store = TieredValueStore.from_dense(
            dense, TieredSpec(shard_rows=256, cache_slots=4)
        )
    else:
        qt = quant.QuantizedTable.from_dense(dense, storage)
        want = quant.dequantize_rows_np(np.asarray(qt.q)[rows],
                                        np.asarray(qt.scale)[rows])
        got_dense = lookup.read_rows_fp32(qt, rows)
        store = TieredValueStore.from_dense(
            dense, TieredSpec(shard_rows=256, cache_slots=4,
                              quant=storage)
        )
    got_store = lookup.read_rows_fp32(store, rows)
    np.testing.assert_array_equal(got_dense, want)
    np.testing.assert_allclose(got_store, want, atol=1e-6)


# ---------------------------------------------------------------------------
# the serve engine: tenant isolation, writeback, zero recompilation
# ---------------------------------------------------------------------------

def _tiny_cfg(**lram_kw):
    lram_kw.setdefault("query_norm", "rms")
    lram_kw.setdefault("interp_impl", "reference")
    return ModelConfig(
        name="tiny-overlay", family="dense", num_layers=2, d_model=32,
        num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=97,
        objective="clm", remat=False, lram_layers=(1,),
        lram=lram.memffn_config(32, 16, **lram_kw),
    )


@pytest.fixture(scope="module")
def tiny_lram_model():
    cfg = _tiny_cfg()
    params, state = transformer.init(KEY, cfg)
    return cfg, params, state


def test_engine_rejects_overlay_without_memory_arch():
    cfg = configs.get_smoke_config("qwen2-1.5b")
    params, state = transformer.init(KEY, cfg)
    with pytest.raises(ValueError, match="memory arch"):
        ServeEngine(params, state, cfg,
                    EngineConfig(slots=1, max_len=8, overlay_rows=4))


def test_empty_overlay_is_bit_exact_vs_no_overlay(tiny_lram_model):
    """An anonymous trace through an overlay-enabled engine produces
    bit-identical tokens AND logits to the overlay-disabled engine: the
    empty-pack correction is exactly zero, not merely small."""
    cfg, params, state = tiny_lram_model
    trace = synthetic_trace(np.random.default_rng(0), 4, vocab_size=97,
                            max_prompt=6, max_gen=5)
    plain = ServeEngine(params, state, cfg,
                        EngineConfig(slots=2, max_len=12)).run(trace)
    overlaid = ServeEngine(
        params, state, cfg,
        EngineConfig(slots=2, max_len=12, overlay_rows=4),
    ).run(trace)
    for a, b in zip(plain.requests, overlaid.requests):
        assert a.id == b.id and a.tokens == b.tokens
        np.testing.assert_array_equal(a.first_logits, b.first_logits)


def test_retire_frees_overlay_and_never_recompiles(tiny_lram_model):
    """Slot retirement detaches the tenant (packs zeroed, no leak) and the
    whole admit/attach/retire/detach cycle reuses ONE decode executable —
    the fixed-shape-pack guarantee."""
    cfg, params, state = tiny_lram_model
    trace = synthetic_trace(np.random.default_rng(1), 5, vocab_size=97,
                            max_prompt=6, max_gen=5, tenants=2)
    engine = ServeEngine(params, state, cfg,
                         EngineConfig(slots=2, max_len=12, overlay_rows=6))
    report = engine.run(trace)
    mgr = engine.overlays
    assert mgr.attached == 0
    assert (mgr.ids == -1).all() and (mgr.deltas == 0.0).all()
    assert mgr.stats["attaches"] == mgr.stats["detaches"] > 0
    assert mgr.stats["writebacks"] > 0
    assert engine._decode._cache_size() == 1
    # overlay telemetry rides the report rows + summary
    assert report.overlay is not None and report.overlay["tenants"] == 2
    assert any(r[0] == "serve_overlay" for r in report.rows())
    assert report.summary(cfg.name)["overlay"]["attaches"] > 0


def test_overlay_correction_reaches_decode_logits(tiny_lram_model):
    """Deterministic forced-hit probe: a pack whose ids cover the rows one
    decode step actually visits must move that step's logits; the same
    pack emptied must not."""
    cfg, params, state = tiny_lram_model
    engine = ServeEngine(params, state, cfg,
                         EngineConfig(slots=1, max_len=12, overlay_rows=8))
    tok = jnp.array([[5]], jnp.int32)
    pos = jnp.array([3], jnp.int32)
    empty_ids = jnp.asarray(np.full_like(engine.overlays.ids, -1))
    empty_deltas = jnp.asarray(np.zeros_like(engine.overlays.deltas))
    cache = transformer.init_cache(cfg, 1, 12)
    logits0, _, access = engine._decode(tok, pos, cache, empty_ids,
                                        empty_deltas)
    visited = np.unique(np.asarray(access[0])[0].reshape(-1))[:8]
    ids = np.full_like(engine.overlays.ids, -1)
    deltas = np.zeros_like(engine.overlays.deltas)
    ids[0, 0, :len(visited)] = visited
    deltas[0, 0, :len(visited)] = 5.0
    cache = transformer.init_cache(cfg, 1, 12)
    logits1, _, _ = engine._decode(tok, pos, cache, jnp.asarray(ids),
                                   jnp.asarray(deltas))
    assert not np.array_equal(np.asarray(logits1), np.asarray(logits0))


def test_writeback_pack_deltas_match_base_table(tiny_lram_model):
    """After serving one tenant, re-attaching them fills the pack with
    delta = dequant(overlay row) - base row, checked directly against the
    model's value table (not through the manager's own reader)."""
    cfg, params, state = tiny_lram_model
    engine = ServeEngine(params, state, cfg,
                         EngineConfig(slots=1, max_len=14, overlay_rows=32,
                                      overlay_write_lr=1.0))
    engine.run([Request(id=0, prompt=np.arange(1, 7, dtype=np.int32),
                        max_new_tokens=6, tenant_id="A")])
    ov = engine.overlays.get("A")
    assert ov.num_rows > 0 and ov.writes > 0
    engine.overlays.attach(0, "A", tick=99)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    values = [v for path, v in flat
              if "lram" in str(path) and "values" in str(path)]
    assert len(values) == 1
    base = np.asarray(values[0], np.float32)
    packed = ov.packed_rows(0)
    assert engine.overlays.ids[0, 0, :len(packed)].tolist() == packed
    for j, r in enumerate(packed):
        np.testing.assert_array_equal(
            engine.overlays.deltas[0, 0, j],
            ov.read(0, r) - base[r],
        )


@pytest.mark.slow
def test_mixed_tenants_match_each_tenant_alone(tiny_lram_model):
    """Acceptance: a mixed-tenant continuous-batching run produces
    per-tenant tokens AND first logits bit-identical to each tenant
    running alone against base + their overlay."""
    cfg, params, state = tiny_lram_model
    trace = synthetic_trace(np.random.default_rng(3), 4, vocab_size=97,
                            max_prompt=6, max_gen=6)
    for i, req in enumerate(trace):
        req.tenant_id = f"T{i}"
    ecfg = EngineConfig(slots=2, max_len=12, overlay_rows=6)
    mixed = ServeEngine(params, state, cfg, ecfg).run(trace)
    for req in trace:
        alone = ServeEngine(params, state, cfg, ecfg).run([req])
        got = next(r for r in mixed.requests if r.id == req.id)
        want = alone.requests[0]
        assert got.tokens == want.tokens
        np.testing.assert_array_equal(got.first_logits, want.first_logits)


@pytest.mark.slow
def test_overlay_on_quantized_table_engine(tiny_lram_model):
    """Overlay storage follows the plan's storage kind: an int8 base
    table gets int8 overlay rows, and the engine still runs end to end
    with stats accounted."""
    cfg = _tiny_cfg(table_quant="int8")
    params, state = transformer.init(KEY, cfg)
    trace = synthetic_trace(np.random.default_rng(4), 3, vocab_size=97,
                            max_prompt=5, max_gen=4, tenants=2)
    engine = ServeEngine(params, state, cfg,
                         EngineConfig(slots=2, max_len=10, overlay_rows=4))
    report = engine.run(trace)
    assert engine.overlays.storage == "int8"
    for ov in engine.overlays.overlays.values():
        for od in ov.rows:
            for payload, scale in od.values():
                assert payload.dtype == np.int8 and scale is not None
    assert report.overlay["writebacks"] > 0


# ---------------------------------------------------------------------------
# lifecycle: the controller's overlay tick never perturbs in-flight work
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("ttl,budget_kb", [(2, None), (None, 0.25),
                                           (1, 0.25)])
def test_controller_overlay_lifecycle_preserves_generation(
        tiny_lram_model, tmp_path, ttl, budget_kb):
    """Fuzzing the TTL/byte-budget schedule through MemoryController:
    overlays expire/spill/restore between ticks without changing a single
    generated token, because enforcement only offloads detached tenants
    and spill files restore losslessly on re-attach."""
    cfg, params, state = tiny_lram_model
    trace = synthetic_trace(np.random.default_rng(5), 6, vocab_size=97,
                            max_prompt=6, max_gen=6, tenants=2)
    ecfg = EngineConfig(slots=2, max_len=12, overlay_rows=6)
    want = {r.id: r.tokens for r in
            ServeEngine(params, state, cfg, ecfg).run(trace).requests}
    ctl = memctl.MemoryController(memctl.LifecyclePolicy(
        tenant_ttl_ticks=ttl,
        tenant_budget_bytes=(int(budget_kb * 1024)
                             if budget_kb is not None else None),
        overlay_spill_dir=str(tmp_path),
    ))
    engine = ServeEngine(params, state, cfg, ecfg, controller=ctl)
    got = {r.id: r.tokens for r in engine.run(trace).requests}
    assert got == want
    assert all(e["event"].startswith("overlay_") for e in ctl.events)
    assert all(e["action"] == "spill" for e in ctl.events)
    stats = engine.overlays.stats
    if ctl.events:
        assert stats["spills"] == len(ctl.events)


@pytest.mark.slow
def test_serve_cli_multitenant_e2e(tmp_path):
    """The serve CLI end to end: multi-tenant trace, overlay lifecycle
    flags, persistence across a relaunch."""
    from repro.launch import serve

    args = ["--smoke", "--batch", "2", "--prompt-len", "4", "--gen", "3",
            "--tenants", "2", "--overlay-rows", "6",
            "--overlay-ttl", "50", "--overlay-budget-kb", "64",
            "--overlay-dir", str(tmp_path / "ov")]
    report = serve.main(args)
    assert report.overlay is not None
    assert report.overlay["tenants"] >= 1
    saved = os.listdir(tmp_path / "ov")
    assert any(f.startswith("overlay_") and f.endswith(".npz")
               for f in saved)
    report2 = serve.main(args)  # relaunch restores the parked overlays
    assert report2.overlay["tenants"] >= 1
