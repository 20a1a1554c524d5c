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

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs, optim
from repro.core import indexing
from repro.kernels import e8_lookup, gather_interp, tiered_gather
from repro.launch.train import build_train_step
from repro.models import transformer

LOG2_ROWS, M, TOP_K = 20, 64, 32
TRAIN_BATCH, TRAIN_SEQ, HEADS = 8, 128, 32   # lram-bert-medium: w/16 heads
TOKENS = TRAIN_BATCH * TRAIN_SEQ * HEADS     # gathers per train step
SHARD_ROWS, CACHE_SLOTS = 8192, 32           # lram-tiered's TieredSpec
STORAGE = {"fp32": jnp.float32, "int8": jnp.int8, "fp8": jnp.float8_e4m3fn}


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e 2x2 host, with the persistent
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
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel"
    return compiled


@pytest.mark.parametrize("storage", sorted(STORAGE))
def test_dense_gather_compiles(one_chip, storage):
    rows = 2**LOG2_ROWS
    table = _shape(one_chip, (rows, M), STORAGE[storage])
    idx = _shape(one_chip, (TOKENS, TOP_K), jnp.int32)
    w = _shape(one_chip, (TOKENS, TOP_K), jnp.float32)
    if storage == "fp32":
        _compile(lambda v, i, ww: gather_interp.gather_interp_pallas(v, i, ww),
                 table, idx, w)
    else:
        scale = _shape(one_chip, (rows,), jnp.float32)
        _compile(
            lambda q, s, i, ww: gather_interp.gather_interp_quant_pallas(
                q, s, i, ww),
            table, scale, idx, w,
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
            cache, idx, slots, w,
        )
    else:
        scale = _shape(one_chip, (cache_rows,), jnp.float32)
        _compile(
            lambda c, sc, i, s, ww: tiered_gather.tiered_gather_quant_pallas(
                c, sc, i, s, ww, shard_rows=SHARD_ROWS),
            cache, scale, idx, slots, w,
        )


def test_e8_query_kernel_compiles(one_chip):
    spec = indexing.choose_torus(LOG2_ROWS)
    q = _shape(one_chip, (TOKENS, 8), jnp.float32)
    _compile(lambda qq: e8_lookup.lram_query_pallas(qq, spec, TOP_K), q)


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
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 2**30
