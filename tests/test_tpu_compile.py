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
from repro.kernels import e8_lookup, gather_interp, tiered_gather
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


def test_pallas_train_step_compiles(one_chip, monkeypatch):
    """The jitted train step of the paper's model with the Pallas gather:
    the kernel is inside the step the chip runs."""
    # the registry cell asks the backend whether to interpret; here the
    # backend is the CPU, the target is the chip
    monkeypatch.setattr(gather_interp, "interpret_mode", lambda: False)
    cfg = configs.get_config("lram-bert-medium")
    cfg = dataclasses.replace(
        cfg, lram=dataclasses.replace(cfg.lram, interp_impl="pallas")
    )
    params, mstate = jax.eval_shape(
        lambda: transformer.init(jax.random.PRNGKey(0), cfg)
    )
    opt = jax.eval_shape(optim.adam_init, params)

    def place(tree):
        return jax.tree.map(lambda s: _shape(one_chip, s.shape, s.dtype),
                            tree)

    batch = {k: _shape(one_chip, (TRAIN_BATCH, TRAIN_SEQ), jnp.int32)
             for k in ("tokens", "labels")}
    step = build_train_step(cfg, optim.OptimConfig())
    compiled = step.lower(place(params), place(opt), place(mstate),
                          _shape(one_chip, (), jnp.float32), batch).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the kernel keeps its name, and its forward its scope, in the step
    assert re.search(r'%lram_gather(\.\d+)? = .*op_name="[^"]*lram\)?/gather/',
                     text)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 2**30


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
