"""Where the program's time can be read by name: the named scopes of the
memory layer and the optimizer in the compiled train step, `repro.obs`
spans as host events of a `jax.profiler` trace, and spans inside the
tiered store's `io_callback` bodies."""

import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, data, memstore, obs, optim
from repro.kernels import scatter_add
from repro.launch.train import build_train_step
from repro.memstore import TieredSpec, TieredValueStore
from repro.models import transformer

WRAPPER = re.compile(r"\b(jvp|transpose|vmap|remat)\(")


@pytest.fixture(autouse=True)
def _obs_off():
    obs.disable()
    yield
    obs.disable()


def _entry_op_names(hlo: str) -> list[str]:
    """The `op_name` of every instruction of the entry computation."""
    entry = hlo[hlo.index("\nENTRY "):]
    entry = entry[: entry.index("\n}")]
    return re.findall(r'op_name="((?:[^"\\]|\\.)*)"', entry)


def _plain(path: str) -> str:
    """`jit(f)/transpose(jvp(lram))/gather/x` -> `jit(f)/lram/gather/x`:
    the transformation wrappers dropped, what they wrap kept."""
    while WRAPPER.search(path):
        m = WRAPPER.search(path)
        depth, i = 1, m.end()
        while depth:
            depth += {"(": 1, ")": -1}.get(path[i], 0)
            i += 1
        inner = path[m.end():i - 1]
        path = path[:m.start()] + inner + path[i:]
    return re.sub(r"//+", "/", path)


@pytest.mark.parametrize("placement,table_backward", [
    ("reference", "scatter-add"),   # the table gradient, in the program
    ("tiered", "io_callback"),      # the store's host write-back
])
def test_train_step_ops_carry_lookup_and_optimizer_scopes(placement,
                                                          table_backward):
    cfg = configs.with_placement(configs.get_smoke_config("lram-bert-small"),
                                 placement)
    dcfg = data.DataConfig(vocab_size=cfg.vocab_size, seq_len=8,
                           global_batch=2, kind="facts", objective="mlm")
    params, state = transformer.init(jax.random.PRNGKey(0), cfg)
    batch = jax.tree.map(jnp.asarray, data.get_batch(dcfg, step=0))
    hlo = build_train_step(cfg, optim.OptimConfig(lr=1e-3)).lower(
        params, optim.adam_init(params), state, jnp.zeros(()), batch
    ).compile().as_text()
    names = _entry_op_names(hlo)
    plain = [_plain(n) for n in names]

    def under(scope):
        return [p for p in plain if f"/{scope}/" in p + "/"]

    for scope in ("lram/query_norm", "lram/query", "lram/gather", "optim",
                  "optim/clip"):
        assert under(scope), f"no compiled op under {scope}"
    for phase in ("decode", "distance", "undo_isometry", "top_k", "encode"):
        assert under(f"lram/query/{phase}"), phase
    # the backward of the table gather keeps its forward's scope
    assert any(n.startswith("jit(train_step)/transpose(")
               and "/lram/gather/" in _plain(n)
               and n.endswith(table_backward)
               for n in names), table_backward
    assert not under("lram/overlay")  # no overlay outside the serve engine


@pytest.mark.parametrize("placement", ["reference", "pallas"])
def test_table_gradient_kernel_carries_gather_scope_and_name(monkeypatch,
                                                             placement):
    """On a chip the table gradient is the `lram_scatter_add` kernel: its
    ops keep the forward's `lram/gather` scope and the kernel's name.  The
    CPU runs it interpreted: the `pallas` placement's backward does so on
    its own; the `reference` gather, which takes XLA's scatter on the CPU,
    is asked to."""
    if placement == "reference":
        rows_add = scatter_add.scatter_add_rows
        monkeypatch.setattr(
            scatter_add, "scatter_add_rows",
            lambda *a, interpret=None: rows_add(*a, interpret=True))
    cfg = configs.with_placement(configs.get_smoke_config("lram-bert-small"),
                                 placement)
    dcfg = data.DataConfig(vocab_size=cfg.vocab_size, seq_len=8,
                           global_batch=2, kind="facts", objective="mlm")
    params, state = transformer.init(jax.random.PRNGKey(0), cfg)
    batch = jax.tree.map(jnp.asarray, data.get_batch(dcfg, step=0))
    hlo = build_train_step(cfg, optim.OptimConfig(lr=1e-3)).lower(
        params, optim.adam_init(params), state, jnp.zeros(()), batch
    ).compile().as_text()
    # every computation's: the interpreted kernel is a loop
    names = re.findall(r'op_name="((?:[^"\\]|\\.)*)"', hlo)
    kernel = [_plain(n) for n in names
              if n.startswith("jit(train_step)/transpose(")
              and "lram_scatter_add" in n]
    assert kernel and all("/lram/gather/lram_scatter_add/" in n + "/"
                          for n in kernel)
    assert not [n for n in names if "/gather/" in _plain(n)
                and n.endswith("scatter-add")]


def test_armed_span_is_a_host_event_of_a_profile(tmp_path):
    obs.configure()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("test.scoped_span"):
            jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    profile = jax.profiler.ProfileData.from_file(path)
    host = [ev.name for plane in profile.planes
            if not plane.name.startswith("/device:")
            for line in plane.lines for ev in line.events]
    assert "test.scoped_span" in host


def test_span_opens_an_annotation_only_when_armed(monkeypatch):
    opened = []

    class Annotation:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    with obs.span("off.span"):
        pass
    assert opened == []
    obs.configure()
    with obs.span("on.outer"):
        with obs.span("on.inner"):
            pass
    assert opened == ["on.outer", "on.inner"]


def test_jitted_tiered_lookup_records_host_callback_spans():
    rng = np.random.default_rng(0)
    dense = rng.standard_normal((1024, 8)).astype(np.float32)
    store = TieredValueStore.from_dense(
        dense, TieredSpec(shard_rows=128, cache_slots=2))
    store.writeback_lr = 0.1
    idx = jnp.asarray(rng.integers(0, 1024, size=(3, 4)), jnp.int32)
    w = jnp.asarray(rng.random((3, 4)), jnp.float32)

    @jax.jit
    def loss(w_):
        return jnp.sum(memstore.tiered_interp(store, idx, w_) ** 2)

    obs.configure()
    out = jax.block_until_ready(jax.value_and_grad(loss)(w))
    spans = {sp.name: sp for sp in obs.tracer().finished}
    assert spans["memstore.gather_rows"].attrs == {"rows": 12}
    assert spans["memstore.writeback"].attrs == {"rows": 12}
    assert spans["memstore.gather_rows"].dur_s > 0
    want = np.sum((np.asarray(w)[..., None] * dense[np.asarray(idx)]).sum(1)
                  ** 2)
    np.testing.assert_allclose(out[0], want, rtol=1e-5)
