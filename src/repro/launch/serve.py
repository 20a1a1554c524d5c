"""Serving driver: a thin CLI over the continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch lram-tiered --smoke \
        --mode continuous --json

Builds a mixed-length request trace (`repro.serving.synthetic_trace`:
random prompt/generation lengths, optional Poisson arrivals via `--rate`)
and replays it through `repro.serving.ServeEngine`:

  * `--mode continuous` (default) — slot-based dynamic batching: sequences
    are admitted into and retired from a fixed pool of decode slots every
    step, with no recompilation (per-slot position vector, bucketed
    batch=1 prefill spliced into the slotted KV cache).
  * `--mode static` — the legacy fixed-batch loop for comparison: a batch
    is admitted only when every slot is free, so the longest sequence in a
    batch blocks the whole pool (head-of-line blocking).

Tiered memory (`lram-tiered` & friends): the cache is warmed before the
first prefill, each step's lattice accesses prefetch the next step's
shards for the union of in-flight sequences, and per-request decode cache
hit-rates ride the report.

Lifecycle (`repro.memctl`, docs/lifecycle.md): `--ckpt-dir` restores a
trained checkpoint, `--grow-to LOG2` pre-grows the table so checkpoints
taken after a `--grow-at` training run restore cleanly, `--placement`
overrides the lookup placement, and `--hbm-budget-mb` / `--spill-at-tick`
attach a MemoryController that migrates a dense table to the tiered store
live, between decode ticks, without dropping in-flight requests.

Per-tenant memory (`repro.serving.overlay`, docs/serving.md): `--tenants N`
assigns trace requests to a pool of N tenants and `--overlay-rows K` gives
each tenant a K-row copy-on-write overlay per lram layer over the shared
base table — attached at admission, written back every decode tick,
retired with the slot, zero recompilation.  `--overlay-ttl` /
`--overlay-budget-kb` add lifecycle enforcement through the controller,
and `--overlay-dir` persists overlays beside the checkpoint shards.

`--json` emits one machine-readable summary document whose `rows` mirror
the benchmark harness columns (name, us_per_call, derived — the schema
`benchmarks/run.py --json` shares; see `benchmarks.run.validate_summary`),
plus per-step latencies, p50/p99, tokens/sec, and per-request records.
"""

from __future__ import annotations

import argparse
import json
import os

import jax
import numpy as np

from repro import configs, memctl, obs
from repro.checkpoint import CheckpointManager
from repro.launch.cache import use_compile_cache
from repro.models import transformer
from repro.serving import EngineConfig, ServeEngine, synthetic_trace


def build_argparser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="lram-tiered")
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--mode", choices=["continuous", "static"],
                   default="continuous")
    p.add_argument("--batch", type=int, default=4,
                   help="decode slots (continuous) / batch size (static)")
    p.add_argument("--prompt-len", type=int, default=16,
                   help="max prompt length in the trace")
    p.add_argument("--gen", type=int, default=16,
                   help="max generation budget per request")
    p.add_argument("--requests", type=int, default=None,
                   help="trace size (default: 2x --batch)")
    p.add_argument("--rate", type=float, default=0.0,
                   help="offered load in requests/sec (0 = all at t=0)")
    p.add_argument("--fixed-len", action="store_true",
                   help="pin every request to (--prompt-len, --gen) instead "
                        "of the mixed-length trace")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--placement", default="",
                   choices=["", "reference", "pallas", "tiered", "sharded",
                            "sharded-tiered"],
                   help="override the memory arch's lookup placement "
                        "(LRAMConfig.interp_impl) — e.g. serve lram-tiered "
                        "dense with --placement reference to demo the "
                        "HBM-budget spill")
    p.add_argument("--ckpt-dir", default="",
                   help="restore params from this checkpoint dir before "
                        "serving (e.g. one written by repro.launch.train)")
    p.add_argument("--grow-to", type=int, default=0, metavar="LOG2",
                   help="grow the memory table to 2^LOG2 locations before "
                        "restoring — serve a checkpoint taken after a "
                        "--grow-at training run")
    p.add_argument("--hbm-budget-mb", type=float, default=0.0,
                   help="spill a dense memory table to the tiered store "
                        "when its size exceeds this budget (live, between "
                        "decode ticks; repro.memctl)")
    p.add_argument("--spill-at-tick", type=int, default=-1,
                   help="deterministically spill dense->tiered at this "
                        "decode tick (demo/testing trigger)")
    p.add_argument("--tenants", type=int, default=0,
                   help="assign each trace request a tenant id from a pool "
                        "of this size (per-tenant memory overlays; 0 = "
                        "anonymous trace)")
    p.add_argument("--overlay-rows", type=int, default=0,
                   help="per-tenant overlay capacity in rows per lram "
                        "layer (0 = off; defaults to 8 when --tenants > 0)")
    p.add_argument("--overlay-write-lr", type=float, default=0.1,
                   help="decode-step Hebbian writeback rate into the "
                        "tenant overlay")
    p.add_argument("--overlay-ttl", type=int, default=0,
                   help="expire a detached tenant overlay after this many "
                        "idle decode ticks (0 = never)")
    p.add_argument("--overlay-budget-kb", type=float, default=0.0,
                   help="total overlay byte budget; LRU detached tenants "
                        "are offloaded beyond it (0 = unlimited)")
    p.add_argument("--overlay-dir", default="",
                   help="persist tenant overlays here (and spill/restore "
                        "through it); defaults to <--ckpt-dir>/overlays "
                        "when a checkpoint dir is given")
    p.add_argument("--metrics-dir", default="",
                   help="arm the observability layer (repro.obs): spans "
                        "stream to <dir>/metrics.jsonl, a Prometheus "
                        "textfile snapshot lands at <dir>/metrics.prom")
    p.add_argument("--profile-dir", default="",
                   help="jax.profiler capture dir for the serve.run span "
                        "(needs --metrics-dir)")
    p.add_argument("--json", action="store_true",
                   help="emit a machine-readable summary (benchmark-harness "
                        "row format + per-step latency + cache hit-rates)")
    return p


def main(argv=None):
    args = build_argparser().parse_args(argv)
    use_compile_cache()
    if args.metrics_dir:
        obs.configure(metrics_dir=args.metrics_dir,
                      profile_dir=args.profile_dir or None)
    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))

    if args.placement:
        cfg = configs.with_placement(cfg, args.placement)

    rng = np.random.default_rng(args.seed)
    key = jax.random.PRNGKey(args.seed)
    params, state = transformer.init(key, cfg)
    if args.grow_to:
        params, cfg, _ = memctl.grow_model(params, cfg, 2**args.grow_to)
    if args.ckpt_dir:
        step, restored = CheckpointManager(args.ckpt_dir).restore(
            {"params": params, "model_state": state}
        )
        if restored is None:
            raise SystemExit(f"no restorable checkpoint in {args.ckpt_dir}")
        params, state = restored["params"], restored["model_state"]
        print(json.dumps({"restored_step": step}))

    overlay_rows = args.overlay_rows
    if overlay_rows == 0 and args.tenants > 0:
        overlay_rows = 8
    overlay_dir = args.overlay_dir
    if not overlay_dir and args.ckpt_dir and overlay_rows > 0:
        overlay_dir = os.path.join(args.ckpt_dir, "overlays")

    controller = None
    if (args.hbm_budget_mb > 0 or args.spill_at_tick >= 0
            or args.overlay_ttl > 0 or args.overlay_budget_kb > 0):
        controller = memctl.MemoryController(memctl.LifecyclePolicy(
            hbm_budget_bytes=(int(args.hbm_budget_mb * 2**20)
                              if args.hbm_budget_mb > 0 else None),
            spill_at_tick=(args.spill_at_tick
                           if args.spill_at_tick >= 0 else None),
            tenant_ttl_ticks=(args.overlay_ttl
                              if args.overlay_ttl > 0 else None),
            tenant_budget_bytes=(int(args.overlay_budget_kb * 1024)
                                 if args.overlay_budget_kb > 0 else None),
            overlay_spill_dir=overlay_dir or None,
        ))

    num_requests = (2 * args.batch if args.requests is None
                    else args.requests)
    trace = synthetic_trace(
        rng, num_requests,
        vocab_size=cfg.vocab_size,
        max_prompt=args.prompt_len,
        max_gen=args.gen,
        rate=args.rate,
        mixed=not args.fixed_len,
        tenants=args.tenants,
    )
    engine = ServeEngine(params, state, cfg, EngineConfig(
        slots=args.batch,
        max_len=args.prompt_len + args.gen,
        mode=args.mode,
        overlay_rows=overlay_rows,
        overlay_write_lr=args.overlay_write_lr,
    ), controller=controller)
    if engine.overlays is not None and overlay_dir:
        engine.overlays.spill_dir = overlay_dir
        restored_overlays = engine.overlays.load_all(overlay_dir)
        if restored_overlays:
            print(json.dumps({"restored_overlays": restored_overlays}))
    report = engine.run(trace)
    if engine.overlays is not None and overlay_dir:
        engine.overlays.save_all(overlay_dir)
    if controller is not None and controller.events:
        print(json.dumps({"lifecycle": controller.events}))
    if args.metrics_dir:
        obs.flush()

    if args.json:
        print(json.dumps(report.summary(cfg.name)))
    else:
        rec = {
            "mode": report.mode,
            "requests": len(report.requests),
            "generated_tokens": report.generated_tokens,
            "tokens_per_sec": round(report.tokens_per_sec, 2),
            "decode_p50_ms": round(report.p50_ms(), 3),
            "decode_p99_ms": round(report.p99_ms(), 3),
        }
        if report.cache:
            rec["cache_hit_rate"] = report.cache["hit_rate"]
        if report.overlay:
            rec["overlay"] = {k: report.overlay[k] for k in
                              ("tenants", "hit_rate", "bytes_per_tenant",
                               "writebacks")}
        print(json.dumps(rec))
    return report


if __name__ == "__main__":
    main()
