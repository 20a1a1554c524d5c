"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train \
        --arch lram-bert-small --smoke --steps 200 --batch 8 --seq 64 \
        --ckpt-dir /tmp/ckpt --ckpt-every 50

Wires every substrate together: config -> init -> (mesh + GSPMD sharding if
>1 device) -> jitted train_step (loss + grad [+ compression] + Adam with the
paper's 10x memory-value LR) -> stateless data -> checkpoint/auto-resume ->
heartbeat/straggler log -> failure injection (--simulate-failure-at), after
which a relaunch resumes bit-exact from the latest valid checkpoint.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs, data, memctl, obs, optim
from repro.checkpoint import CheckpointManager
from repro.core import lookup
from repro.distributed import context, fault, sharding
from repro.launch import mesh as mesh_lib
from repro.launch.cache import use_compile_cache
from repro.models import transformer


def lram_segments(cfg) -> list[str]:
    """Segment names of the lram memory layers (telemetry keys)."""
    return [f"seg{si}" for si, seg in enumerate(transformer.layer_plan(cfg))
            if seg[0] == "memory" and seg[2] == "lram"]


def telemetry_rows_per_bin(num_locations: int, *, max_bins: int = 4096) -> int:
    """Coarsen per-row counters so the carried pytree stays <= max_bins
    bins (num_locations is a power of two, so this always divides)."""
    rpb = 1
    while num_locations // rpb > max_bins:
        rpb *= 2
    return rpb


def init_telemetry(cfg):
    """One usage-counter pytree per lram segment (the carried `tel`)."""
    n = cfg.lram.num_locations
    rpb = telemetry_rows_per_bin(n)
    return {name: memctl.telemetry_init(n, rows_per_bin=rpb)
            for name in lram_segments(cfg)}


def build_train_step(cfg, opt_cfg, mesh=None, compression="none",
                     telemetry=False):
    """The jitted step.  With `telemetry=True` the step carries a usage
    pytree (`tel`, from `init_telemetry`) like optimizer state: the loss
    runs with `collect_access=True` and each lram segment's access indices
    are scatter-added into its counters in-graph
    (`memctl.telemetry_update`) — the only mode that changes the traced
    computation; the plain step is byte-identical to the pre-obs one."""
    def _finish(loss, metrics, residual, grads, params, opt_state):
        if compression != "none":
            comp = {"kind": compression, "rho": 0.01, "residual": residual}
            grads, comp = optim.compress_gradients(grads, comp)
            residual = comp["residual"]
        new_params, new_opt, stats = optim.adam_update(
            grads, opt_state, params, opt_cfg
        )
        return new_params, new_opt, residual, \
            {**metrics, **stats, "loss": loss}

    if telemetry:
        def train_step(params, opt_state, model_state, residual, batch,
                       tel):
            (loss, (new_model_state, metrics, accesses)), grads = \
                jax.value_and_grad(transformer.loss_fn, has_aux=True)(
                    params, model_state, batch, cfg, train=True,
                    collect_access=True,
                )
            tel = {
                name: (memctl.telemetry_update(t, accesses[name][0])
                       if name in accesses else t)
                for name, t in tel.items()
            }
            new_params, new_opt, residual, metrics = _finish(
                loss, metrics, residual, grads, params, opt_state
            )
            return (new_params, new_opt, new_model_state, residual,
                    metrics, tel)
    else:
        def train_step(params, opt_state, model_state, residual, batch):
            (loss, (new_model_state, metrics)), grads = jax.value_and_grad(
                transformer.loss_fn, has_aux=True
            )(params, model_state, batch, cfg, train=True)
            new_params, new_opt, residual, metrics = _finish(
                loss, metrics, residual, grads, params, opt_state
            )
            return new_params, new_opt, new_model_state, residual, metrics

    if mesh is None:
        return jax.jit(train_step, donate_argnums=(0, 1))
    pspec = sharding.batch_pspec(mesh)
    batch_sh = NamedSharding(mesh, P(pspec[0] if len(pspec) else None))
    batch_in = jax.tree.map(lambda _: batch_sh, {"tokens": 0, "labels": 0})
    in_sh = (None, None, None, None, batch_in)
    if telemetry:
        in_sh = in_sh + (None,)
    return jax.jit(train_step, in_shardings=in_sh, donate_argnums=(0, 1))


def evaluate(params, model_state, cfg, dcfg, *, steps=4):
    """Held-out loss, and fact recall on a probe set.  Both run jitted at
    the training batch size: the lattice lookup's (tokens, 232, 8)
    neighbour tensor of a larger batch does not fit one chip's HBM."""
    loss_fn = jax.jit(lambda p, s, b: transformer.loss_fn(
        p, s, b, cfg, train=False)[0])
    forward = jax.jit(lambda p, s, b: transformer.forward(p, s, b, cfg)[0])
    losses = []
    table = data.make_fact_table(dcfg)
    for i in range(steps):
        batch = jax.tree.map(
            jnp.asarray, data.get_batch(dcfg, step=10_000_000 + i,
                                        table=table)
        )
        losses.append(float(loss_fn(params, model_state, batch)))
    probe = data.synthetic.fact_eval_batch(dcfg, n=64, table=table)
    hits = total = 0
    for lo in range(0, len(probe["tokens"]), dcfg.global_batch):
        chunk = {k: jnp.asarray(v[lo:lo + dcfg.global_batch])
                 for k, v in probe.items()}
        pred = jnp.argmax(forward(params, model_state, chunk), axis=-1)
        mask = chunk["labels"] != data.synthetic.IGNORE
        hits += int(jnp.where(mask, pred == chunk["labels"], False).sum())
        total += int(mask.sum())
    return float(np.mean(losses)), hits / total


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="lram-bert-small")
    p.add_argument("--smoke", action="store_true",
                   help="use the reduced same-family config")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--lr", type=float, default=1e-4)
    p.add_argument("--memory-lr-mult", type=float, default=10.0)
    p.add_argument("--compression", default="none",
                   choices=["none", "int8", "topk"])
    p.add_argument("--ckpt-dir", default="")
    p.add_argument("--ckpt-every", type=int, default=100)
    p.add_argument("--eval-every", type=int, default=0)
    p.add_argument("--grow-at", default="",
                   help="memory-growth schedule STEP:NEW_LOG2[,STEP:...] — "
                        "grow the value table online at the given steps "
                        "(repro.memctl; e.g. '100:19,500:20')")
    p.add_argument("--simulate-failure-at", type=int, default=-1)
    p.add_argument("--telemetry", action="store_true",
                   help="carry in-graph memory-usage counters through the "
                        "train step and log utilisation_report rows "
                        "beside the loss (lram archs)")
    p.add_argument("--metrics-dir", default="",
                   help="arm the observability layer (repro.obs): spans "
                        "stream to <dir>/metrics.jsonl, a Prometheus "
                        "textfile snapshot lands at <dir>/metrics.prom")
    p.add_argument("--profile-dir", default="",
                   help="jax.profiler capture dir for marked spans "
                        "(needs --metrics-dir)")
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--use-mesh", action="store_true",
                   help="shard over all available devices")
    p.add_argument("--placement", default="",
                   choices=["", "reference", "pallas", "tiered", "sharded",
                            "sharded-tiered"],
                   help="override the memory arch's lookup placement "
                        "(LRAMConfig.interp_impl) — e.g. 'sharded' with "
                        "--use-mesh row-shards the table over the 'model' "
                        "mesh axis")
    args = p.parse_args(argv)
    use_compile_cache()

    if args.metrics_dir:
        obs.configure(metrics_dir=args.metrics_dir,
                      profile_dir=args.profile_dir or None)

    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    if args.placement:
        cfg = configs.with_placement(cfg, args.placement)
    if args.telemetry and cfg.lram is None:
        raise SystemExit(f"--telemetry needs a memory arch; {cfg.name} "
                         f"has no LRAM layer")
    dcfg = data.DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch, objective=cfg.objective, seed=args.seed,
    )
    opt_cfg = optim.OptimConfig(lr=args.lr,
                                memory_lr_mult=args.memory_lr_mult)

    mesh = None
    if args.use_mesh and jax.device_count() > 1:
        mesh = mesh_lib.make_host_mesh()
        print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")
    # the ambient mesh (the `sharded` placement shard_maps over it) holds
    # for this run only
    prev_mesh = context.get_mesh()
    if mesh is not None:
        context.set_mesh(mesh)
    try:
        return _train(args, cfg, dcfg, opt_cfg, mesh)
    finally:
        context.set_mesh(prev_mesh)


def _train(args, cfg, dcfg, opt_cfg, mesh):
    """The loop behind `main`; returns (params, history), one history
    entry {step, loss, grad_norm} per logged step."""
    key = jax.random.PRNGKey(args.seed)
    params, model_state = transformer.init(key, cfg)

    def bind_stores(params):
        # write-back-capable placements (tiered, sharded-tiered —
        # discovered via the resolved lookup plan) own their sparse
        # optimizer step (write-back SGD at the paper's memory LR); the
        # dense Adam below never sees their tables
        stores = (
            lookup.find_stores(params)
            if any(p.table_update == "writeback"
                   for p in lookup.model_plans(cfg))
            else []
        )
        for _, store in stores:
            store.writeback_lr = args.lr * args.memory_lr_mult
            store.warm()
        return stores

    stores = bind_stores(params)
    if mesh is not None:
        params = sharding.shard_params(params, mesh, model_cfg=cfg)
    opt_state = optim.adam_init(params)

    def init_residual(params):
        residual = optim.compression_init(params,
                                          args.compression)["residual"]
        if residual is None:
            residual = jnp.zeros(())  # jit-friendly placeholder
        return residual

    residual = init_residual(params)

    controller = None
    if args.grow_at:
        controller = memctl.MemoryController(memctl.LifecyclePolicy(
            grow_at=memctl.parse_grow_at(args.grow_at)
        ))
        if cfg.lram is None:
            raise SystemExit(f"--grow-at needs a memory arch; {cfg.name} "
                             f"has no LRAM layer")

    start_step = 0
    mgr = None
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, keep=3)
        latest = mgr.latest_step()
        if latest is not None:
            if controller is not None:
                # growths that fired before the checkpoint was taken must
                # be re-applied first, so the restore target (and its
                # grow-on-restore path) has the grown shape
                params, cfg, opt_state, changed = controller.catch_up(
                    latest, params, cfg, opt_state
                )
                if changed:
                    stores = bind_stores(params)
                    residual = init_residual(params)
            tree = {"params": params, "opt": opt_state,
                    "model_state": model_state}
            step_found, restored = mgr.restore(tree)
            if restored is not None:
                params = restored["params"]
                opt_state = restored["opt"]
                model_state = restored["model_state"]
                start_step = step_found
                print(f"resumed from step {start_step}")

    step_fn = build_train_step(cfg, opt_cfg, mesh, args.compression,
                               telemetry=args.telemetry)
    tel = init_telemetry(cfg) if args.telemetry else None
    monitor = fault.HeartbeatMonitor(num_hosts=jax.process_count())
    timer = fault.StepTimer()
    history = []

    for step in range(start_step, args.steps):
        if controller is not None:
            params, cfg, opt_state, changed = controller.on_train_step(
                step, params, cfg, opt_state
            )
            if changed:
                # the grown table changes traced shapes (and, for stores,
                # capacity behind the same handles): re-bind write-back,
                # re-jit the step against the new config, and re-size the
                # compression residual (error feedback restarts at zero —
                # it mirrors params, including any grown dense table)
                stores = bind_stores(params)
                step_fn = build_train_step(cfg, opt_cfg, mesh,
                                           args.compression,
                                           telemetry=args.telemetry)
                residual = init_residual(params)
                if tel is not None:
                    # appended bins start dead; the utilisation log then
                    # shows the post-growth recovery curve directly
                    tel = {
                        name: memctl.grow_telemetry(
                            t, cfg.lram.num_locations
                        ) for name, t in tel.items()
                    }
                ev = controller.events[-1]
                print(json.dumps({
                    "grow": f"2^{ev['new_log2']}", "step": step,
                    "pause_s": ev["pause_s"],
                }))
        if step == args.simulate_failure_at:
            if mgr:
                mgr.wait()
            raise fault.SimulatedFailure(
                f"injected failure at step {step} (relaunch to resume)"
            )
        t0 = time.time()
        batch = jax.tree.map(jnp.asarray, data.get_batch(dcfg, step=step))
        with obs.span("train.step", step=step):
            if tel is None:
                params, opt_state, model_state, residual, metrics = step_fn(
                    params, opt_state, model_state, residual, batch
                )
            else:
                (params, opt_state, model_state, residual, metrics,
                 tel) = step_fn(
                    params, opt_state, model_state, residual, batch, tel
                )
        dt = time.time() - t0
        timer.record(dt)
        monitor.heartbeat(jax.process_index(), dt)
        if step % args.log_every == 0 or step == args.steps - 1:
            slow = " STRAGGLER" if timer.is_outlier(dt) else ""
            history.append({"step": step, "loss": float(metrics["loss"]),
                            "grad_norm": float(metrics["grad_norm"])})
            rec = {
                "step": step,
                "loss": round(float(metrics["loss"]), 4),
                "xent": round(float(metrics["xent"]), 4),
                "grad_norm": round(float(metrics["grad_norm"]), 3),
                "sec": round(dt, 3),
            }
            if stores:
                rec["cache_hit"] = round(
                    float(np.mean([s.hit_rate() for _, s in stores])), 4
                )
            print(json.dumps(rec) + slow)
            if tel is not None:
                # hot/cold/dead utilisation beside the loss, one report
                # row set per lram segment (drained at the log boundary:
                # the counters themselves stay on device, in-graph)
                for name, t in tel.items():
                    rows = memctl.utilisation_report(
                        t, prefix=f"util_{name}"
                    )
                    print(json.dumps({"step": step,
                                      "utilisation_report": rows}))
                    s = memctl.utilisation_summary(t)
                    obs.gauge("train.util_dead_frac").set(s["dead_frac"])
                    obs.gauge("train.util_hot_mass").set(s["hot_mass"])
                    obs.gauge("train.util_cold_frac").set(s["cold_frac"])
        if mgr and args.ckpt_every and (step + 1) % args.ckpt_every == 0:
            mgr.save(step + 1,
                     {"params": params, "opt": opt_state,
                      "model_state": model_state},
                     blocking=False)
        if args.eval_every and (step + 1) % args.eval_every == 0:
            eval_loss, recall = evaluate(params, model_state, cfg, dcfg)
            print(json.dumps({"eval_loss": round(eval_loss, 4),
                              "fact_recall": round(recall, 4)}))

    if mgr:
        mgr.save(args.steps, {"params": params, "opt": opt_state,
                              "model_state": model_state})
        mgr.wait()
    eval_loss, recall = evaluate(params, model_state, cfg, dcfg)
    print(json.dumps({"final_eval_loss": round(eval_loss, 4),
                      "final_fact_recall": round(recall, 4)}))
    if args.metrics_dir:
        obs.flush()
    return params, history


if __name__ == "__main__":
    main()
