"""Compile the main path's Pallas kernels, and the one-chip train step that
runs them, for a described TPU v5e chip.

Nothing runs: the chip's own compiler, which ships with jax, compiles for a
chip that is described and not attached.  What it refuses (a block or DMA
slice not aligned to the tiling, a primitive Mosaic cannot lower, a program
that does not fit the chip) fails here, in interpret-mode-only CI, before a
chip run would.  Sizes are the real ones: the 2^20 x 64 table of
`lram-bert-medium` / `lram-tiered`, top_k=32, and the token count of
`chip_smoke.py`'s train phase.

The topology is described inside a fixture (never at import): only one
process at a time may load the TPU compiler library, and every test worker
imports this file.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from repro import configs, optim
from repro.core import indexing
from repro.distributed import context, sharding
from repro.kernels import e8_lookup, gather_interp, scatter_add, tiered_gather
from repro.launch.mesh import make_mesh
from repro.launch.train import build_train_step
from repro.models import moe, transformer

LOG2_ROWS, M, TOP_K = 20, 64, 32
TRAIN_BATCH, TRAIN_SEQ, HEADS = 8, 128, 32   # lram-bert-medium: w/16 heads
TOKENS = TRAIN_BATCH * TRAIN_SEQ * HEADS     # gathers per train step
SHARD_ROWS, CACHE_SLOTS = 8192, 32           # lram-tiered's TieredSpec
STORAGE = {"fp32": jnp.float32, "int8": jnp.int8, "fp8": jnp.float8_e4m3fn}


@pytest.fixture(scope="module")
def v5e_2x2():
    """A described v5e 2x2 host (four chips), with the persistent
    compilation cache off (a compile for a described chip is written to
    the cache but cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # whatever the cause, there is no chip lib
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    """One device of the described host."""
    return SingleDeviceSharding(v5e_2x2.devices[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, kernel: str):
    """Compile `fn`; the program must hold a Mosaic kernel named `kernel`
    (the `name=` of its `pallas_call`, which a profile's events carry)."""
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel"
    assert re.search(rf"%{kernel}(\.\d+)? = .*tpu_custom_call", text), \
        f"no kernel named {kernel}"
    return compiled


@pytest.mark.parametrize("storage", sorted(STORAGE))
def test_dense_gather_compiles(one_chip, storage):
    rows = 2**LOG2_ROWS
    table = _shape(one_chip, (rows, M), STORAGE[storage])
    idx = _shape(one_chip, (TOKENS, TOP_K), jnp.int32)
    w = _shape(one_chip, (TOKENS, TOP_K), jnp.float32)
    if storage == "fp32":
        _compile(lambda v, i, ww: gather_interp.gather_interp_pallas(v, i, ww),
                 table, idx, w, kernel="lram_gather")
    else:
        scale = _shape(one_chip, (rows,), jnp.float32)
        _compile(
            lambda q, s, i, ww: gather_interp.gather_interp_quant_pallas(
                q, s, i, ww),
            table, scale, idx, w, kernel="lram_gather_quant",
        )


@pytest.mark.parametrize("storage", sorted(STORAGE))
def test_tiered_gather_compiles(one_chip, storage):
    cache_rows = CACHE_SLOTS * SHARD_ROWS
    cache = _shape(one_chip, (cache_rows, M), STORAGE[storage])
    slots = _shape(one_chip, (2**LOG2_ROWS // SHARD_ROWS,), jnp.int32)
    idx = _shape(one_chip, (TOKENS, TOP_K), jnp.int32)
    w = _shape(one_chip, (TOKENS, TOP_K), jnp.float32)
    if storage == "fp32":
        _compile(
            lambda c, i, s, ww: tiered_gather.tiered_gather_pallas(
                c, i, s, ww, shard_rows=SHARD_ROWS),
            cache, idx, slots, w, kernel="lram_tiered_gather",
        )
    else:
        scale = _shape(one_chip, (cache_rows,), jnp.float32)
        _compile(
            lambda c, sc, i, s, ww: tiered_gather.tiered_gather_quant_pallas(
                c, sc, i, s, ww, shard_rows=SHARD_ROWS),
            cache, scale, idx, slots, w, kernel="lram_tiered_gather_quant",
        )


def test_e8_query_kernel_compiles(one_chip):
    spec = indexing.choose_torus(LOG2_ROWS)
    q = _shape(one_chip, (TOKENS, 8), jnp.float32)
    _compile(lambda qq: e8_lookup.lram_query_pallas(qq, spec, TOP_K), q,
             kernel="lram_e8_query")


@pytest.mark.parametrize("entries", [2**21, 2**22])
def test_scatter_add_kernel_compiles(one_chip, entries):
    """The table gradient's kernel over sorted entries, at the train cells'
    2,097,152 (BERT) and 4,194,304 (Moonlight) entries a step."""
    rows = 2**LOG2_ROWS
    b, c = scatter_add.tiles(rows, M, entries)
    _compile(lambda i, w, g: scatter_add._block_sums(rows, b, c, i, w, g),
             _shape(one_chip, (entries,), jnp.int32),
             _shape(one_chip, (entries,), jnp.float32),
             _shape(one_chip, (entries, M), jnp.float32),
             kernel="lram_scatter_add")


def _train_step_text(one_chip, cfg, batch_rows, mesh=None):
    """The compiled train step: on one chip, or with the batch over the
    data axis of `mesh` and the parameters as `sharding.param_pspecs`
    places them (as `train.py --use-mesh` does)."""
    params, mstate = jax.eval_shape(
        lambda: transformer.init(jax.random.PRNGKey(0), cfg)
    )
    rows = (batch_rows, TRAIN_SEQ)
    if mesh is None:
        params = jax.tree.map(lambda s: _shape(one_chip, s.shape, s.dtype),
                              params)
        batch = {k: _shape(one_chip, rows, jnp.int32)
                 for k in ("tokens", "labels")}
        other = one_chip
    else:
        specs = sharding.param_pspecs(params, mesh, model_cfg=cfg)
        params = jax.tree.map(lambda s, p: _shape(
            NamedSharding(mesh, p), s.shape, s.dtype), params, specs)
        data = NamedSharding(mesh, sharding.batch_pspec(mesh))
        batch = {k: _shape(data, rows, jnp.int32)
                 for k in ("tokens", "labels")}
        other = NamedSharding(mesh, jax.sharding.PartitionSpec())
    opt = jax.eval_shape(optim.adam_init, params)
    opt = jax.tree.map(lambda s: _shape(
        getattr(s, "sharding", None) or other, s.shape, s.dtype), opt)
    mstate = jax.tree.map(lambda s: _shape(other, s.shape, s.dtype), mstate)
    step = build_train_step(cfg, optim.OptimConfig(), mesh)
    compiled = step.lower(params, opt, mstate, _shape(other, (), jnp.float32),
                          batch).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 2**30
    return compiled.as_text()


def test_reference_train_step_builds_table_gradient_with_kernel(
        one_chip, monkeypatch):
    """The train cell's step (the `reference` placement, batch 16): the
    table gradient is the `lram_scatter_add` kernel under the gather's
    scope, and no scatter writes into the table's shape."""
    monkeypatch.setattr(scatter_add, "interpret_mode", lambda: False)
    text = _train_step_text(one_chip,
                            configs.get_config("lram-bert-medium"), 16)
    assert re.search(
        r'%lram_scatter_add(\.\d+)? = .*op_name="[^"]*lram\)*/gather/', text)
    table = "f32[%d,%d]" % (2**LOG2_ROWS, M)
    assert not [ln for ln in text.splitlines()
                if re.search(r"= %s\S* scatter\(" % re.escape(table), ln)]


def test_pallas_train_step_compiles(one_chip, monkeypatch):
    """The jitted train step of the paper's model with the Pallas gather:
    the kernel is inside the step the chip runs."""
    # the registry cells ask the backend whether to interpret; here the
    # backend is the CPU, the target is the chip
    # (the backward's scatter-add takes the gather's flag)
    monkeypatch.setattr(gather_interp, "interpret_mode", lambda: False)
    cfg = configs.get_config("lram-bert-medium")
    cfg = dataclasses.replace(
        cfg, lram=dataclasses.replace(cfg.lram, interp_impl="pallas")
    )
    text = _train_step_text(one_chip, cfg, TRAIN_BATCH)
    assert "tpu_custom_call" in text
    # the kernels keep their names, and their scope, in the step
    for kernel in ("lram_gather", "lram_scatter_add"):
        assert re.search(
            r'%%%s(\.\d+)? = .*op_name="[^"]*lram\)*/gather/' % kernel, text)


def test_reference_train_step_compiles_on_a_data_mesh(v5e_2x2, monkeypatch):
    """The train cell's step over the described host as `--use-mesh` lays
    it out (2 data x 2 model): GSPMD never partitions a Mosaic kernel, so
    the table gradient's kernel runs in a shard_map over `data`, each chip
    on its half of the batch's 2,097,152 entries, and one all-reduce under
    the gather's scope adds the halves."""
    monkeypatch.setattr(scatter_add, "interpret_mode", lambda: False)
    mesh = make_mesh((2, 2), ("data", "model"), devices=v5e_2x2.devices)
    monkeypatch.setattr(context, "_MESH", mesh)
    text = _train_step_text(None, configs.get_config("lram-bert-medium"),
                            16, mesh)
    half = 16 * TRAIN_SEQ * HEADS * TOP_K // 2
    table = "f32[%d,%d]" % (2**LOG2_ROWS, M)
    kernels = [ln for ln in text.splitlines()
               if re.search(r"%lram_scatter_add(\.\d+)? = .*tpu_custom_call",
                            ln)]
    assert kernels and all("s32[1,%d]" % half in ln and re.search(
        r'op_name="[^"]*lram\)*/gather/', ln) for ln in kernels)
    assert [ln for ln in text.splitlines()
            if re.search(r"= %s\S* all-reduce\(" % re.escape(table), ln)
            and re.search(r'op_name="[^"]*lram\)*/gather/', ln)]
    assert not [ln for ln in text.splitlines()
                if re.search(r"= %s\S* scatter\(" % re.escape(table), ln)]


@pytest.mark.parametrize("experts", [8, 2])
def test_expert_layer_compiles_sharded_over_model(v5e_2x2, monkeypatch,
                                                  experts):
    """Mixtral's expert layer at its published widths, forward and
    backward, over a 1 x 4 mesh of the described host: 8 experts, two a
    chip, or 2 experts, a quarter of each one's hidden width a chip.  The
    grouped products compile to Mosaic kernels inside the shard_map, and no
    chip gathers the expert weights it does not hold."""
    monkeypatch.setattr(moe, "interpret_mode", lambda: False)
    cfg = dataclasses.replace(configs.get_config("mixtral-8x7b"),
                              num_experts=experts, experts_held=experts)
    mesh = make_mesh((1, 4), ("data", "model"), devices=v5e_2x2.devices)
    params, state = jax.eval_shape(
        lambda: moe.moe_init(jax.random.PRNGKey(0), cfg))
    specs = sharding.param_pspecs({"moe": params}, mesh)["moe"]
    params = jax.tree.map(lambda s, p: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=NamedSharding(mesh, p)), params, specs)
    x = _shape(NamedSharding(mesh, jax.sharding.PartitionSpec()),
               (1, 256, cfg.d_model), jnp.float32)

    def loss(p, x):
        return jnp.sum(moe.moe_apply(p, state, x, cfg, train=True)[0])

    monkeypatch.setattr(context, "_MESH", mesh)
    compiled = _compile(jax.grad(loss, argnums=(0, 1)), params, x,
                        kernel="gmm")
    text = compiled.as_text()
    assert re.search(r"%tgmm(\.\d+)? = .*tpu_custom_call", text)
    stack = "f32[%d,%d,%d]" % (experts, cfg.d_model, cfg.d_ff)
    gathers = [ln for ln in text.splitlines() if "all-gather" in ln]
    assert not [ln for ln in gathers if stack in ln], gathers
