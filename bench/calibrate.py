"""The readings that a cell's `correct` limits are set from, on the chip at
the cell's own size, many seeds in one process.

    python3 bench/calibrate.py train --cell train-bert-medium-mlm128 \
        --seeds 11 12 ... --control-seeds 11 12 13
    python3 bench/calibrate.py serve --cell serve-tiered-chat \
        serve-tiered-offline --seeds 11 12 ... --control-seeds 11 12 13 \
        --seconds 10

For each seed it prints one JSON line: the program's readings (the numbers
its runs compare), and for a control seed the control's readings (the plain
reference computed in the configuration's `control_dtype`, with its
products' operands rounded to `control_products` where it states one, in
the program's place) and, for training, those of the fault "half of the
batch left out, the mean taken over the rest" (planted in the reference).  The
lower end of a limit is the largest program reading, the upper end the
smallest control or fault reading (see PERF.md).  The benchmark's own runs
never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def train(cell: dict, seeds, control_seeds) -> None:
    import jax
    import jax.numpy as jnp

    drv = harness.driver(cell["workload"])
    conf, mix = cell["config"], cell["traffic"]
    step = drv.build(conf)
    for seed in seeds:
        t0 = time.perf_counter()
        prog, carry, _ = drv.first_steps(step, conf, mix, seed)
        del carry
        gc.collect()
        with jax.default_matmul_precision("highest"):
            ref = drv.reference_run(conf, mix, seed)
        line = {"seed": seed, "program": drv.readings(prog, ref)}
        if seed in control_seeds:
            ctl = drv.reference_run(conf, mix, seed,
                                    dtype=jnp.dtype(conf["control_dtype"]))
            line["control"] = drv.readings(ctl, ref)
            with jax.default_matmul_precision("highest"):
                half = drv.reference_run(conf, mix, seed,
                                         batch_rows=mix["batch"] // 2)
            line["half_batch"] = drv.readings(half, ref)
        line["seconds"] = time.perf_counter() - t0
        line["losses"] = {"program": prog["losses"],
                          "reference": ref["losses"]}
        print(json.dumps(line), flush=True)


def serve(cells: list[dict], seeds, control_seeds, seconds: float) -> None:
    """Cells of one configuration and engine shape share one engine: each
    seed's requests (`seconds` of a Poisson mix, one batch of a batch mix)
    are served, then the engine is freed and the reference reads them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serving import EngineConfig, Request, ServeEngine

    first = cells[0]
    drv = harness.driver(first["workload"])
    _, weights, gen = drv._imports()
    conf, wl = first["config"], first["workload"]
    shape = ("slots", "max_len", "mode", "check_tokens")
    if any(c["config"] != conf or any(c["workload"][k] != wl[k]
                                      for k in shape) for c in cells):
        raise SystemExit("cells calibrated together share a configuration "
                         "and an engine shape")
    pcfg = harness.program_config(conf)
    params, state = weights.make(conf, conf["weight_seed"])
    params = drv.place_table(params, pcfg)
    engine = ServeEngine(params, state, pcfg, EngineConfig(
        slots=wl["slots"], max_len=wl["max_len"], mode=wl["mode"]))
    vocab = conf["vocab_size"]
    lo = min(gen.length_range(c["traffic"])[0] for c in cells)
    hi = max(gen.length_range(c["traffic"])[1] for c in cells)
    engine.run([Request(id=i, prompt=np.arange(n, dtype=np.int32) % vocab,
                        max_new_tokens=2)
                for i, n in enumerate(drv.warm_lengths(lo, hi,
                                                       wl["max_len"]))])
    results = []
    for cell in cells:
        for seed in seeds:
            reqs = gen.requests(cell["traffic"], vocab, seed, seconds)
            t0 = time.perf_counter()
            report = engine.run([Request(id=r.id, prompt=r.prompt,
                                         max_new_tokens=r.max_new_tokens,
                                         arrival_s=r.due_s) for r in reqs])
            picked = drv.sample(report.requests, seed, wl["check_tokens"])
            length = max(len(r.prompt) + len(r.tokens) - 1 for r in picked)
            results.append((cell["name"], seed, picked, min(
                wl["max_len"], 1 << (length - 1).bit_length()),
                time.perf_counter() - t0, len(report.requests)))
    del engine, params
    gc.collect()
    jax.clear_caches()
    # the configuration's control, and the reference in its storage dtype
    # alone (products at the reference's precision), for the record
    ctl = jnp.dtype(conf["control_dtype"])
    controls = {"control": (ctl, conf.get("control_products")),
                "control_storage_only": (ctl, None)}
    for name, seed, picked, length, secs, n in results:
        t0 = time.perf_counter()
        got = drv.reference_gaps(conf, picked, length, controls=(
            controls if seed in control_seeds else None))
        print(json.dumps({"cell": name, "seed": seed, "requests": n,
                          "checked_requests": len(picked),
                          "checked_tokens": int(np.sum(
                              [len(r.tokens) for r in picked])),
                          "engine_s": secs,
                          "reference_s": time.perf_counter() - t0,
                          **got}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("kind", choices=("train", "serve"))
    ap.add_argument("--cell", nargs="+", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    harness.program_path()
    harness.use_compile_cache()
    cells = [harness.cell(name) for name in args.cell]
    if args.kind == "train":
        for cell in cells:
            train(cell, args.seeds, set(args.control_seeds))
    else:
        serve(cells, args.seeds, set(args.control_seeds), args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
