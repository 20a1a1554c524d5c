"""Observability layer (repro.obs): registry semantics, off-is-free,
device accumulate->drain under jit, span nesting, exporter schemas,
bit-exactness of the metrics-on serve path, HLO identity of the train
step, and thread-safety of the tiered store's stat counters."""

import concurrent.futures
import json
import math
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import importlib

from repro import configs, obs
from repro.memstore import TieredSpec, TieredValueStore
from repro.models import transformer
from repro.obs import export

# `obs.registry` the accessor shadows the submodule on the package
reg = importlib.import_module("repro.obs.registry")
from repro.serving import EngineConfig, ServeEngine, synthetic_trace


@pytest.fixture(autouse=True)
def _obs_off():
    """Every test starts and ends with the process default: disabled."""
    obs.disable()
    yield
    obs.disable()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_semantics():
    r = reg.MetricsRegistry()
    c = r.counter("c")
    c.inc()
    c.inc(2.5)
    assert c.get() == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = r.gauge("g")
    g.set(7)
    g.add(-2)
    assert g.get() == 5.0
    h = r.histogram("h", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 3.0, 100.0):
        h.observe(v)
    snap = h.snapshot()
    # le semantics: 0.5->le1, 1.0->le1 (boundary counts in its bucket),
    # 3.0->le4, 100->+Inf
    assert snap["counts"] == [2, 0, 1, 1]
    assert snap["count"] == 4 and snap["sum"] == pytest.approx(104.5)
    assert h.quantile(0.5) == 1.0
    assert h.quantile(1.0) == math.inf


def test_registry_same_name_same_metric_kind_conflict_raises():
    r = reg.MetricsRegistry()
    assert r.counter("x") is r.counter("x")
    with pytest.raises(ValueError, match="already registered"):
        r.gauge("x")


def test_histogram_rejects_bad_buckets_and_bad_drain():
    with pytest.raises(ValueError):
        reg.Histogram("h", buckets=())
    with pytest.raises(ValueError):
        reg.Histogram("h", buckets=(1.0, 1.0))
    h = reg.Histogram("h", buckets=(1.0, 2.0))
    with pytest.raises(ValueError, match="expected 3 bucket counts"):
        h.merge_counts([1, 2])


def test_disabled_registry_is_null_and_free():
    r = reg.MetricsRegistry(enabled=False)
    c = r.counter("c")
    assert c is reg.NULL_METRIC
    assert c is r.histogram("h")  # one shared singleton for every kind
    c.inc()
    c.observe(1.0)
    c.set(2.0)
    assert c.get() == 0.0
    assert r.snapshot() == {}
    # the process default is the disabled state
    assert not obs.enabled()
    assert obs.counter("anything") is reg.NULL_METRIC
    with obs.span("nothing") as sp:
        sp.set_attr("k", 1)  # vanishes
    assert obs.tracer().span_count() == 0
    doc = obs.metrics_doc()
    export.validate_metrics_doc(doc)
    assert doc["enabled"] is False and doc["metrics"] == {}


def test_counter_thread_safety():
    r = reg.MetricsRegistry()
    c = r.counter("c")

    def worker():
        for _ in range(1000):
            c.inc()

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.get() == 8000.0


# ---------------------------------------------------------------------------
# device-side accumulate -> host drain
# ---------------------------------------------------------------------------

def test_jit_accum_drains_into_host_histogram():
    # binary-exact bounds: the device path buckets in float32, so a bound
    # like 0.001 would round differently than the host's float64 compare
    bounds = (0.25, 1.0, 4.0)
    n_slots = len(bounds) + 1

    @jax.jit
    def step(acc, values):
        return reg.hist_bucket_add(acc, values, bounds)

    acc = reg.accum_init(n_slots)
    values = jnp.asarray([0.125, 0.25, 2.0, 100.0, 0.5])
    acc = step(acc, values)
    acc = step(acc, values)

    h = reg.Histogram("h", buckets=bounds)
    h.merge_counts(np.asarray(acc), total=2 * float(values.sum()))
    # boundary 0.25 lands in its own (le) bucket on both paths
    ref = reg.Histogram("ref", buckets=bounds)
    for _ in range(2):
        for v in values.tolist():
            ref.observe(v)
    assert h.snapshot()["counts"] == ref.snapshot()["counts"]
    assert h.sum == pytest.approx(ref.sum, rel=1e-6)


def test_jit_accum_add_counts_indices():
    @jax.jit
    def step(acc, idx):
        return reg.accum_add(acc, idx)

    acc = reg.accum_init(8)
    acc = step(acc, jnp.asarray([[0, 3], [3, 7]]))
    np.testing.assert_array_equal(
        np.asarray(acc), [1, 0, 0, 2, 0, 0, 0, 1]
    )
    acc = reg.accum_add(acc, jnp.asarray([1]), w=jnp.asarray([2.5]))
    assert float(acc[1]) == 2.5


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

def test_span_nesting_parent_links_and_counter_deltas():
    r = obs.configure(enabled=True)
    with obs.span("outer", tag="a") as so:
        obs.counter("work.items").inc(3)
        with obs.span("inner") as si:
            obs.counter("work.items").inc(2)
    spans = {s.name: s for s in obs.tracer().finished}
    assert spans["inner"].parent_id == spans["outer"].span_id
    assert spans["outer"].parent_id is None
    assert spans["inner"].metrics == {"work.items": 2.0}
    assert spans["outer"].metrics == {"work.items": 5.0}
    assert spans["outer"].attrs == {"tag": "a"}
    assert spans["outer"].dur_s >= spans["inner"].dur_s >= 0
    assert so is spans["outer"] and si is spans["inner"]
    assert r.counter("work.items").get() == 5.0


def test_span_events_validate_and_roundtrip(tmp_path):
    obs.configure(metrics_dir=str(tmp_path))
    with obs.span("serve.run", mode="continuous"):
        with obs.span("serve.decode_tick", tick=0):
            obs.counter("serve.tokens").inc(4)
    obs.emit_event("memctl.spill", tick=0, placement="dense->tiered")
    obs.flush()

    events = export.read_jsonl(str(tmp_path / obs.JSONL_NAME))
    kinds = {e["kind"] for e in events}
    assert kinds == {"span", "event", "metrics"}
    by_name = {e["name"]: e for e in events if e["kind"] == "span"}
    assert by_name["serve.decode_tick"]["parent"] == by_name["serve.run"]["id"]
    assert by_name["serve.decode_tick"]["metrics"]["serve.tokens"] == 4.0
    snap = [e for e in events if e["kind"] == "metrics"][-1]["metrics"]
    assert snap["serve.tokens"]["value"] == 4.0

    prom = (tmp_path / obs.PROM_NAME).read_text()
    export.validate_prometheus_text(prom)
    assert "repro_serve_tokens_total 4.0" in prom


# ---------------------------------------------------------------------------
# exporter schemas
# ---------------------------------------------------------------------------

def test_validate_event_rejects_malformed_docs():
    for bad in (
        "not a dict",
        {"kind": "nope"},
        {"kind": "span", "name": "bad name!", "id": 1, "t0_s": 0,
         "dur_s": 0},
        {"kind": "span", "name": "s", "id": "one", "t0_s": 0, "dur_s": 0},
        {"kind": "span", "name": "s", "id": 1, "t0_s": 0, "dur_s": -1},
        {"kind": "span", "name": "s", "id": 1, "t0_s": 0, "dur_s": 0,
         "metrics": {"m": float("nan")}},
        {"kind": "event", "name": "e"},                       # no t_s
        {"kind": "metrics", "t_s": 0, "metrics": {"m": {"kind": "alien"}}},
        {"kind": "metrics", "t_s": 0,
         "metrics": {"h": {"kind": "histogram", "buckets": [1.0],
                           "counts": [1], "sum": 0.0}}},      # len mismatch
    ):
        with pytest.raises(ValueError):
            export.validate_event(bad)


def test_validate_metrics_doc_accepts_live_and_rejects_corrupt():
    obs.configure(enabled=True)
    obs.counter("a.b").inc()
    obs.histogram("a.lat").observe(0.01)
    doc = obs.metrics_doc()
    export.validate_metrics_doc(doc)
    assert doc["schema"] == export.METRICS_SCHEMA
    for corrupt in (
        {**doc, "schema": "v0"},
        {**doc, "enabled": "yes"},
        {**doc, "spans": -1},
        {**doc, "metrics": {"x": {"kind": "counter", "value": None}}},
        [],
    ):
        with pytest.raises(ValueError):
            export.validate_metrics_doc(corrupt)


def test_jsonl_exporter_appends_and_validates(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    ex = export.JsonlExporter(path)
    ex.write_event("e.one", k=1)
    with pytest.raises(ValueError):
        ex.write({"kind": "span", "name": "s"})  # missing fields
    ex.close()
    ex2 = export.JsonlExporter(path)  # append mode: old events survive
    ex2.write_event("e.two")
    ex2.close()
    assert [e["name"] for e in export.read_jsonl(path)] == ["e.one", "e.two"]
    # a corrupted line fails re-validation with its line number
    with open(path, "a", encoding="utf-8") as f:
        f.write(json.dumps({"kind": "event", "name": "bad"}) + "\n")
    with pytest.raises(ValueError, match="ev.jsonl:3"):
        export.read_jsonl(path)


def test_prometheus_text_families():
    r = reg.MetricsRegistry()
    r.counter("serve.tokens", help="decoded tokens").inc(7)
    r.gauge("memctl.num_locations").set(65536)
    h = r.histogram("serve.decode_step_s", buckets=(0.01, 0.1))
    h.observe(0.005)
    h.observe(0.5)
    text = export.prometheus_text(r)
    export.validate_prometheus_text(text)
    assert "# HELP repro_serve_tokens decoded tokens" in text
    assert "repro_serve_tokens_total 7.0" in text
    assert "repro_memctl_num_locations 65536.0" in text
    # cumulative le buckets end at +Inf == count
    assert 'repro_serve_decode_step_s_bucket{le="0.01"} 1' in text
    assert 'repro_serve_decode_step_s_bucket{le="+Inf"} 2' in text
    assert "repro_serve_decode_step_s_count 2" in text


# ---------------------------------------------------------------------------
# zero-overhead guarantees: bit-exact serving, identical train-step HLO
# ---------------------------------------------------------------------------

def _serve_once(params, state, cfg):
    trace = synthetic_trace(
        np.random.default_rng(3), 4, vocab_size=cfg.vocab_size,
        max_prompt=6, max_gen=5, mixed=True,
    )
    engine = ServeEngine(params, state, cfg,
                         EngineConfig(slots=2, max_len=11))
    report = engine.run(trace)
    return {r.id: list(map(int, r.tokens)) for r in report.requests}


def test_metrics_on_serving_is_bit_exact(tmp_path):
    cfg = configs.get_smoke_config("lram-tiered")
    params, state = transformer.init(jax.random.PRNGKey(0), cfg)
    tokens_off = _serve_once(params, state, cfg)
    obs.configure(metrics_dir=str(tmp_path))
    tokens_on = _serve_once(params, state, cfg)
    doc = obs.metrics_doc()
    assert tokens_on == tokens_off
    # ...and the instrumented layers actually reported
    assert doc["metrics"]["serve.tokens"]["value"] > 0
    assert doc["metrics"]["memstore.fills"]["value"] > 0
    assert doc["spans"] > 0
    events = export.read_jsonl(str(tmp_path / obs.JSONL_NAME))
    assert {"serve.run", "serve.decode_tick", "serve.prefill"} <= {
        e["name"] for e in events if e["kind"] == "span"
    }


def test_serve_latency_counts_from_arrival():
    """`serve.request_latency_s` runs from each request's arrival to its
    retirement and `serve.queue_wait_s` from arrival to admission, both on
    the engine's clock: a request that queues behind another counts its
    wait."""
    cfg = configs.get_smoke_config("lram-tiered")
    params, state = transformer.init(jax.random.PRNGKey(0), cfg)
    trace = synthetic_trace(np.random.default_rng(5), 4,
                            vocab_size=cfg.vocab_size, max_prompt=6,
                            max_gen=4, rate=200.0)
    arrival = {r.id: r.arrival_s for r in trace}
    obs.configure()
    report = ServeEngine(params, state, cfg,
                         EngineConfig(slots=1, max_len=11)).run(trace)
    lat = obs.histogram("serve.request_latency_s")
    wait = obs.histogram("serve.queue_wait_s")
    assert lat.count == wait.count == len(trace)
    assert lat.sum == pytest.approx(sum(r.finish_s - arrival[r.id]
                                        for r in report.requests))
    assert wait.sum == pytest.approx(sum(r.admit_s - arrival[r.id]
                                         for r in report.requests))
    assert wait.sum > 0  # one slot: later arrivals wait for it


def test_train_step_hlo_identical_with_obs_armed():
    """The registry/tracer never enter traced code: the non-telemetry train
    step lowers to byte-identical HLO whether obs is armed or not."""
    from repro import data, optim
    from repro.launch.train import build_train_step

    cfg = configs.get_smoke_config("lram-bert-small")
    dcfg = data.DataConfig(vocab_size=cfg.vocab_size, seq_len=8,
                           global_batch=2, kind="facts", objective="mlm")
    opt_cfg = optim.OptimConfig(lr=1e-3)
    params, state = transformer.init(jax.random.PRNGKey(0), cfg)
    opt_state = optim.adam_init(params)
    batch = jax.tree.map(jnp.asarray, data.get_batch(dcfg, step=0))
    args = (params, opt_state, state, jnp.zeros(()), batch)

    hlo_off = build_train_step(cfg, opt_cfg).lower(*args).as_text()
    obs.configure(enabled=True)
    obs.counter("noise").inc()
    hlo_on = build_train_step(cfg, opt_cfg).lower(*args).as_text()
    assert hlo_on == hlo_off


# ---------------------------------------------------------------------------
# satellite: tiered-store stat counters under the prefetch thread pool
# ---------------------------------------------------------------------------

def test_store_stats_consistent_under_concurrent_prefetch():
    """Regression: `prefetch_last` runs on a ThreadPoolExecutor in the
    sharded serve path while the io_callback gather mutates the same
    stats/LRU dicts.  Hammer both concurrently and check the counters
    add up and the cache invariants hold."""
    rng = np.random.default_rng(0)
    rows, shard_rows, slots = 4096, 256, 4
    dense = rng.normal(size=(rows, 8)).astype(np.float32)
    store = TieredValueStore.from_dense(
        dense, TieredSpec(shard_rows=shard_rows, cache_slots=slots)
    )
    idx_sets = [
        rng.integers(0, rows, size=64).astype(np.int32) for _ in range(24)
    ]
    store.gather_rows_host(idx_sets[0])  # seed last_access

    errors = []

    def hammer_prefetch():
        try:
            for _ in range(200):
                store.prefetch_last()
        except Exception as e:  # pragma: no cover - the regression
            errors.append(e)

    def hammer_gather():
        try:
            for idx in idx_sets:
                got = store.gather_rows_host(idx)
                np.testing.assert_allclose(got, dense[idx], rtol=1e-6)
        except Exception as e:  # pragma: no cover
            errors.append(e)

    with concurrent.futures.ThreadPoolExecutor(max_workers=4) as pool:
        futs = [pool.submit(hammer_prefetch) for _ in range(2)]
        futs += [pool.submit(hammer_gather) for _ in range(2)]
        for f in futs:
            f.result()
    assert not errors
    s = store.stats
    # every counted element is a hit, miss, or uncached — no lost updates
    # (prefetch_last never counts; each gather counts all 64 elements)
    assert s["hits"] + s["misses"] + s["uncached"] == 64 * s["lookups"]
    assert s["lookups"] == 1 + 2 * len(idx_sets)
    assert len(store.resident_shards()) <= slots
    # the cache still serves correct rows after the stampede
    probe = rng.integers(0, rows, size=128).astype(np.int32)
    np.testing.assert_allclose(store.gather_rows_host(probe), dense[probe],
                               rtol=1e-6)
