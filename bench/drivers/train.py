"""Training cells: back-to-back steps of the program's jitted train step.

Set-up makes the weights from `--seed`, builds the step that
`repro.launch.train.build_train_step` returns (params as arguments), and
drives it through its first three steps on batches 0-2 of the traffic,
which compiles it (or reads it from the cache) and records what `correct`
compares.  The window then goes on with batches 3, 4, ... through the same
call, until `--seconds` have passed on the host clock, and ends in
`block_until_ready`; the host dispatches at most two steps ahead of the
device, so the window ends within two steps of `--seconds`.

`correct`: the plain reference (`bench/reference/`) follows the same three
steps from the same seed at full float32 precision, and three numbers are
compared, each against the limit in the workload file:

* `loss_gap`: the largest |loss - reference loss| / |reference loss| over
  the three steps;
* `grad_norm_gap`: the first step's gradient as the optimizer got it
  (Adam's first moment after one step, / (1 - b1)), per leaf: the largest
  |norm - reference norm| / max(reference norm, median leaf's norm);
* `update_norm_gap`: the same for each leaf's change over the three
  steps, leaving out leaves whose reference gradient norm is under 1e-3 of
  the median leaf's (Adam moves those by round-off alone).
"""

from __future__ import annotations

import contextlib
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

_BENCH = Path(__file__).resolve().parents[1]
CHECK_STEPS = 3
IN_FLIGHT = 2     # steps dispatched ahead of the one the host waits for
SMALL_GRAD = 1e-3


def _imports():
    import importlib
    import sys

    if str(_BENCH) not in sys.path:
        sys.path.insert(0, str(_BENCH))
    return (importlib.import_module("harness"),
            importlib.import_module("weights"),
            importlib.import_module("traffic.generate"))


def _leaf_norms(tree) -> dict[str, float]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in xs])([x for _, x in flat])
    return {jax.tree_util.keystr(p): float(n)
            for (p, _), n in zip(flat, jax.device_get(norms))}


def _host_copy(tree) -> dict[str, np.ndarray]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): np.array(x) for p, x in flat}


def _change_norms(tree, before: dict[str, np.ndarray]) -> dict[str, float]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): float(np.linalg.norm(
        (np.asarray(x, np.float64) - before[jax.tree_util.keystr(p)]).ravel()))
        for p, x in flat}


def worst_leaf_gap(got: dict, ref: dict, keep=None) -> tuple[float, str]:
    """max over leaves of |got - ref| / max(ref, median ref), and the leaf."""
    keys = [k for k in ref if keep is None or keep(k)]
    med = float(np.median([ref[k] for k in keys]))
    gaps = {k: abs(got[k] - ref[k]) / max(ref[k], med, 1e-30) for k in keys}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def readings(prog: dict, ref: dict) -> dict:
    """The three compared numbers from two sets of {losses, grad_norms,
    change_norms}."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["losses"], ref["losses"]))
    med = float(np.median(list(ref["grad_norms"].values())))
    g_gap, g_leaf = worst_leaf_gap(prog["grad_norms"], ref["grad_norms"])
    u_gap, u_leaf = worst_leaf_gap(
        prog["change_norms"], ref["change_norms"],
        keep=lambda k: ref["grad_norms"][k] >= SMALL_GRAD * med)
    return {"loss_gap": loss_gap, "grad_norm_gap": g_gap,
            "update_norm_gap": u_gap, "grad_norm_leaf": g_leaf,
            "update_norm_leaf": u_leaf}


def reference_run(conf, mix, seed: int, dtype=jnp.float32,
                  batch_rows=None) -> dict:
    """The plain reference's losses, first clipped gradient norms and
    3-step change norms, per leaf.  `batch_rows` keeps only the first rows
    of each batch (a fault, for the limits' upper readings)."""
    harness, weights, gen = _imports()
    ref = harness.reference(conf["reference"])
    vocab = conf["vocab_size"]
    params, state = weights.make(conf, seed, dtype=dtype)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    step = jax.jit(ref.train_step_fn(conf))
    before = _host_copy(params)
    cdf = gen.zipf_cdf(vocab, mix["zipf_a"])
    losses, grad_norms = [], None
    for i in range(CHECK_STEPS):
        b = gen.train_batch(mix, vocab, seed, i, cdf)
        if batch_rows is not None:
            b = {k: x[:batch_rows] for k, x in b.items()}
        params, state, m, v, loss, grads = step(
            params, state, m, v, jnp.float32(i + 1), b)
        losses.append(float(loss))
        if i == 0:
            grad_norms = _leaf_norms(grads)
        del grads
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": _change_norms(params, before)}


def build(conf):
    """The program's jitted train step for a configuration file."""
    harness, weights, _ = _imports()
    from repro import optim
    from repro.launch import train as train_mod
    from repro.models import transformer

    pcfg = harness.program_config(conf)
    weights.check_layout(conf, *jax.eval_shape(
        lambda k: transformer.init(k, pcfg), jax.random.PRNGKey(0)))
    o = conf["optimizer"]
    opt_cfg = optim.OptimConfig(lr=o["lr"], memory_lr_mult=o["memory_lr_mult"],
                                memory_path=o["memory_path"], b1=o["b1"],
                                b2=o["b2"], eps=o["eps"],
                                grad_clip=o["grad_clip"])
    return train_mod.build_train_step(pcfg, opt_cfg)


def first_steps(step, conf, mix, seed: int):
    """Weights from `seed`, then the first CHECK_STEPS steps through
    `step` on batches 0, 1, ...; returns (the program's readings, the
    state to go on from, feed) where feed(i) is batch i on the device."""
    _, weights, gen = _imports()
    from repro import optim

    vocab = conf["vocab_size"]
    cdf = gen.zipf_cdf(vocab, mix["zipf_a"])

    def feed(i):
        return jax.tree.map(jnp.asarray, gen.train_batch(mix, vocab, seed, i,
                                                         cdf))

    params, model_state = weights.make(conf, seed)
    opt_state = optim.adam_init(params)
    residual = jnp.zeros(())
    before = _host_copy(params)
    prog = {"losses": []}
    for i in range(CHECK_STEPS):
        params, opt_state, model_state, residual, metrics = step(
            params, opt_state, model_state, residual, feed(i))
        prog["losses"].append(float(metrics["loss"]))
        if i == 0:
            b1 = conf["optimizer"]["b1"]
            prog["grad_norms"] = {k: n / (1 - b1) for k, n in
                                  _leaf_norms(opt_state["mu"]).items()}
    prog["change_norms"] = _change_norms(params, before)
    return prog, (params, opt_state, model_state, residual), feed


def run(ctx) -> dict:
    harness, _, _ = _imports()
    conf, mix = ctx.cell["config"], ctx.cell["traffic"]
    limits = ctx.cell["workload"]["limits"]
    step = build(conf)
    seed = ctx.seed
    prog, carry, feed = first_steps(step, conf, mix, seed)
    params, opt_state, model_state, residual = carry
    del carry

    compiles0 = ctx.compiles.count
    trace = {}
    tokens_per_step = mix["batch"] * mix["seq_len"]
    ann = harness.annotate if ctx.trace else None
    losses, n = [], 0
    ctx.window_start = time.perf_counter()
    with harness.profiled(ctx.trace_dir, trace):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < ctx.seconds:
            with ann("bench.batch") if ann else contextlib.nullcontext():
                b = feed(CHECK_STEPS + n)
            with ann("bench.step") if ann else contextlib.nullcontext():
                params, opt_state, model_state, residual, metrics = step(
                    params, opt_state, model_state, residual, b)
            losses.append(metrics["loss"])
            n += 1
            if n > IN_FLIGHT:
                # keep the host at most IN_FLIGHT steps ahead of the device,
                # so that the window ends when --seconds have passed
                losses[n - 1 - IN_FLIGHT].block_until_ready()
        jax.block_until_ready((params, opt_state, model_state))
        elapsed = time.perf_counter() - t0
    window_compiles = ctx.compiles.count - compiles0
    failed = int(np.sum(~np.isfinite(np.asarray(jnp.stack(losses)))))
    peak = harness.memory_peak_bytes(ctx.devices)
    del params, opt_state, model_state, residual, metrics, losses

    with jax.default_matmul_precision("highest"):
        ref = reference_run(conf, mix, seed)
    r = readings(prog, ref)
    checks = [{"name": k, "value": r[k], "limit": limits[k],
               "ok": r[k] <= limits[k]}
              for k in ("loss_gap", "grad_norm_gap", "update_norm_gap")]
    harness.log(check_leaves={k: r[k] for k in ("grad_norm_leaf",
                                                "update_norm_leaf")},
                losses=prog["losses"], reference_losses=ref["losses"])
    return {
        "attempted": n, "failed": failed, "checks": checks,
        "window_compiles": window_compiles, "memory_peak_bytes": peak,
        "trace": trace,
        "end_to_end": {"train_tokens_per_s": n * tokens_per_step / elapsed},
        "inputs": {"steps": n, "elapsed_s": elapsed,
                   "tokens_per_step": tokens_per_step,
                   "seq_len": mix["seq_len"]},
    }
