"""Compile each cell's programs for a described TPU v5e, with no chip, and
print their `memory_analysis()`: what the chip's compiler would refuse,
and how many bytes each program asks for.

    JAX_PLATFORMS=cpu python3 bench/compile_v5e.py train [--batches 8 16 32]
    JAX_PLATFORMS=cpu python3 bench/compile_v5e.py serve [--cell serve-tiered-chat]

`train` compiles the training cell's step (params as arguments, as the
window runs it) at each batch; the largest power of two whose program fits
one chip's HBM is the cell's batch.  `serve` compiles the engine's decode
step and every prefill bucket of the serving cells, with the table's host
store in the program as the engine has it; the weights are arguments here,
where the engine compiles them in as constants.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402

HBM = 16e9


def _one_chip():
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def _specs(tree, sharding):
    import jax

    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                       sharding=sharding),
                        tree)


def _memory(compiled) -> dict:
    m = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    out = {k: int(getattr(m, k)) for k in keys}
    out["total_bytes"] = (out["argument_size_in_bytes"]
                          + out["output_size_in_bytes"]
                          + out["temp_size_in_bytes"]
                          - out["alias_size_in_bytes"])
    return out


def train(cell_name: str, batches: list[int]) -> None:
    import jax
    import jax.numpy as jnp

    from repro import optim
    from repro.launch import train as train_mod
    from repro.models import transformer

    one = _one_chip()
    cell = harness.cell(cell_name)
    conf, mix = cell["config"], cell["traffic"]
    pcfg = harness.program_config(conf)
    params, state = jax.eval_shape(lambda k: transformer.init(k, pcfg),
                                   jax.random.PRNGKey(0))
    opt = jax.eval_shape(optim.adam_init, params)
    step = train_mod.build_train_step(pcfg, optim.OptimConfig())
    for b in batches:
        batch = {k: jax.ShapeDtypeStruct((b, mix["seq_len"]), jnp.int32,
                                         sharding=one)
                 for k in ("tokens", "labels")}
        try:
            compiled = step.lower(
                _specs(params, one), _specs(opt, one), _specs(state, one),
                jax.ShapeDtypeStruct((), jnp.float32, sharding=one),
                batch).compile()
            mem = _memory(compiled)
            print(json.dumps({"program": "train_step", "batch": b, **mem,
                              "fits": mem["total_bytes"] <= HBM}),
                  flush=True)
        except Exception as e:  # the compiler's refusal is the result
            print(json.dumps({"program": "train_step", "batch": b,
                              "refused": str(e).splitlines()[0][:300]}),
                  flush=True)


def serve(cell_name: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import configs
    from repro.core import lookup
    from repro.models import transformer

    sys.path.insert(0, str(harness.BENCH))
    from traffic import generate as gen

    one = _one_chip()
    cell = harness.cell(cell_name)
    conf, mix, wl = cell["config"], cell["traffic"], cell["workload"]
    pcfg = harness.program_config(conf)
    dense = configs.with_placement(pcfg, "reference")
    params, state = jax.eval_shape(lambda k: transformer.init(k, dense),
                                   jax.random.PRNGKey(0))
    mem_seg = next(f"seg{i}" for i, s in enumerate(
        transformer.layer_plan(pcfg)) if s[0] == "memory")
    lr = conf["lram"]
    table = lookup.resolve(pcfg.lram).build_table(
        np.zeros((2 ** lr["log2_locations"], lr["m"]), np.float32))
    rest = jax.tree.map(lambda x: x, params)
    del rest["segments"][mem_seg]["memffn"]["lram"]["values"]

    def with_table(p):
        p = jax.tree.map(lambda x: x, p)
        p["segments"][mem_seg]["memffn"]["lram"]["values"] = table
        return p

    slots, max_len = wl["slots"], wl["max_len"]
    cache = transformer.cache_specs(pcfg, slots, max_len)
    dec = jax.jit(lambda p, s, t, pos, c: transformer.decode_step(
        with_table(p), s, t, pos, c, pcfg), donate_argnums=(4,))
    compiled = dec.lower(
        _specs(rest, one), _specs(state, one),
        jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one),
        _specs(cache, one)).compile()
    print(json.dumps({"program": "decode_step", "slots": slots,
                      "max_len": max_len, **_memory(compiled)}), flush=True)
    lo, hi = gen.length_range(mix)
    for n in sorted({min(1 << (x - 1).bit_length(), max_len)
                     for x in (lo, hi, *[1 << j for j in range(31)
                                         if lo < (1 << j) < hi])}):
        pre = jax.jit(lambda p, s, t: transformer.prefill(
            with_table(p), s, {"tokens": t}, pcfg, max_len))
        compiled = pre.lower(
            _specs(rest, one), _specs(state, one),
            jax.ShapeDtypeStruct((1, n), jnp.int32, sharding=one)).compile()
        print(json.dumps({"program": "prefill", "bucket": n,
                          **_memory(compiled)}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("what", choices=("train", "serve"))
    ap.add_argument("--cell", default=None)
    ap.add_argument("--batches", type=int, nargs="*", default=[8, 16, 32])
    args = ap.parse_args(argv)
    harness.program_path()
    if args.what == "train":
        train(args.cell or "train-bert-medium-mlm128", args.batches)
    else:
        serve(args.cell or "serve-tiered-chat")
    return 0


if __name__ == "__main__":
    sys.exit(main())
