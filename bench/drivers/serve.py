"""Serving cells: the program's `ServeEngine` under a traffic file's
requests.

Set-up makes the weights from the configuration's `weight_seed` (the
engine compiles them into its programs as constants, so they stay the
same from run to run and every program is found in the compile cache),
places the memory table as the configuration states (for `tiered`, the
program's own table builder), builds the engine with the workload file's
slots and `max_len`, and warms it: one request for each prefill bucket the
mix's prompt lengths reach, each decoding one token.

The window hands the engine the requests that `--seed` draws for
`--seconds`:

* `arrival: poisson`: one `ServeEngine.run` over all of them, each due at
  its time on the engine's clock; the run lasts until the last is served.
  TTFT of a request is (admission + prefill) - due time, TPOT is
  (finish - first token) / (tokens - 1);
* `arrival: batch`: the batch, queued at t = 0, replayed by
  `ServeEngine.run` back to back until `--seconds` have passed, and the
  last replay finished.

`correct`: a sample of the finished requests, drawn from the seed and
holding the one with the most tokens, is run through the plain reference
at full float32 precision (dense table, same weights): prompt and served
tokens, all positions at once.  `served_logit_gap` is the widest gap by
which a served token's reference logit lies below the reference's best at
its position; greedy decoding serves the best token, so a sound run gaps
by rounding alone.
"""

from __future__ import annotations

import gc
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

_BENCH = Path(__file__).resolve().parents[1]
REF_BATCH = 4     # sequences per reference call


def _imports():
    import importlib
    import sys

    if str(_BENCH) not in sys.path:
        sys.path.insert(0, str(_BENCH))
    return (importlib.import_module("harness"),
            importlib.import_module("weights"),
            importlib.import_module("traffic.generate"))


def place_table(params, pcfg):
    """The configuration's table placement, built by the program's own
    lookup plan from the dense table (tiered: a host store)."""
    from repro.core import lookup
    from repro.models import transformer

    out = jax.tree.map(lambda x: x, params)
    for si, seg in enumerate(transformer.layer_plan(pcfg)):
        if seg[0] == "memory":
            lram = out["segments"][f"seg{si}"]["memffn"]["lram"]
            lram["values"] = lookup.resolve(pcfg.lram).build_table(
                lram["values"])
    return out


def warm_cache_sync(engine) -> None:
    """Warm the tiered store's device-cache sync for every count of stale
    slots it can upload (each count is a shape of its own), through the
    store's public prefetch."""
    for _, store in engine.stores:
        for n in range(1, store.cache_slots + 1):
            resident = set(store.resident_shards())
            new = [s for s in range(store.num_shards) if s not in resident]
            store.prefetch(np.asarray(new[:n], np.int64) * store.shard_rows,
                           sync_device=True)


def warm_lengths(lo: int, hi: int, cap: int) -> list[int]:
    """One prompt length in [lo, hi] for each power-of-two prefill bucket
    (capped at max_len, as the engine buckets) that [lo, hi] reaches."""
    def bucket(n):
        return min(1 << (n - 1).bit_length(), cap)

    cands = [lo, hi] + [1 << j for j in range(31) if lo < (1 << j) < hi]
    return sorted({bucket(n): n for n in sorted(cands)}.values())


def sample(finished: list, seed: int, tokens: int) -> list:
    """Finished requests drawn from the seed, the longest (most tokens
    served) first, until `tokens` served tokens are held."""
    order = sorted(range(len(finished)), key=lambda i: -len(
        finished[i].tokens))
    rest = np.random.default_rng([int(seed), 4]).permutation(order[1:])
    picked, total = [], 0
    for i in [order[0], *rest]:
        picked.append(finished[i])
        total += len(finished[i].tokens)
        if total >= tokens:
            break
    return picked


def pack(reqs: list, length: int):
    """(tokens, targets) int32 (n, length): prompt and served tokens (the
    last is never fed back), and at each position the served token it
    predicts (-1 where none)."""
    toks = np.zeros((len(reqs), length), np.int32)
    tgt = np.full((len(reqs), length), -1, np.int32)
    for i, r in enumerate(reqs):
        seq = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
        toks[i, :len(seq)] = seq
        s = len(r.prompt)
        tgt[i, s - 1:s - 1 + len(r.tokens)] = r.tokens
    return toks, tgt


def logit_gaps(logits, targets, pick=None):
    """Per row, the widest (best - logit of the picked token) over the
    positions with a target; `pick` defaults to the targets."""
    logits = logits.astype(jnp.float32)
    best = jnp.max(logits, -1)
    chosen = jnp.where(targets >= 0, targets, 0) if pick is None else pick
    got = jnp.take_along_axis(logits, chosen[..., None], -1)[..., 0]
    return jnp.max(jnp.where(targets >= 0, best - got, -jnp.inf), -1)


def reference_gaps(conf, reqs: list, length: int, *, controls=None):
    """The widest served-token gap over `reqs` under the float32 reference;
    `controls` maps a name to (dtype, product dtype or None): for each, the
    gap of the token that the reference computed so puts first at the same
    positions (a control, see `bench/calibrate.py`)."""
    harness, weights, _ = _imports()
    ref = harness.reference(conf["reference"])
    params, state = weights.make(conf, conf["weight_seed"])
    toks, tgt = pack(reqs, length)
    pad = (-len(reqs)) % REF_BATCH
    toks = np.concatenate([toks, np.zeros((pad, length), np.int32)])
    tgt = np.concatenate([tgt, np.full((pad, length), -1, np.int32)])
    fwd = jax.jit(lambda p, s, t: ref.forward(conf, p, s, t)[0])

    def first_choice(prod):
        low_conf = dict(conf, products=prod)
        return jax.jit(lambda p, s, t: jnp.argmax(
            ref.forward(low_conf, p, s, t)[0], -1).astype(jnp.int32))

    low = {name: (first_choice(prod),
                  weights.make(conf, conf["weight_seed"], dtype=dtype))
           for name, (dtype, prod) in (controls or {}).items()}
    gaps = {"served_logit_gap": [], **{n: [] for n in low}}
    gap_fn = jax.jit(logit_gaps)
    for lo in range(0, len(toks), REF_BATCH):
        t, g = jnp.asarray(toks[lo:lo + REF_BATCH]), \
            jnp.asarray(tgt[lo:lo + REF_BATCH])
        with jax.default_matmul_precision("highest"):
            logits = fwd(params, state, t)
        gaps["served_logit_gap"].append(np.asarray(gap_fn(logits, g)))
        for name, (pick_fn, (lp, ls)) in low.items():
            gaps[name].append(np.asarray(gap_fn(logits, g,
                                                pick_fn(lp, ls, t))))
    return {k: float(np.max(np.concatenate(v))) for k, v in gaps.items()}


def run(ctx) -> dict:
    harness, weights, gen = _imports()
    from repro import configs
    from repro.models import transformer
    from repro.serving import EngineConfig, Request, ServeEngine

    conf, mix = ctx.cell["config"], ctx.cell["traffic"]
    wl = ctx.cell["workload"]
    pcfg = harness.program_config(conf)
    dense_cfg = configs.with_placement(pcfg, "reference")
    weights.check_layout(conf, *jax.eval_shape(
        lambda k: transformer.init(k, dense_cfg), jax.random.PRNGKey(0)))
    params, state = weights.make(conf, conf["weight_seed"])
    params = place_table(params, pcfg)
    ecfg = EngineConfig(slots=wl["slots"], max_len=wl["max_len"],
                        mode=wl["mode"])
    engine = ServeEngine(params, state, pcfg, ecfg)
    vocab = conf["vocab_size"]
    lo, hi = gen.length_range(mix)
    warm = [Request(id=i, prompt=np.arange(n, dtype=np.int32) % vocab,
                    max_new_tokens=2)
            for i, n in enumerate(warm_lengths(lo, hi, wl["max_len"]))]
    engine.run(warm)
    warm_cache_sync(engine)
    if ctx.trace:
        harness.wrap_annotated(engine, "_prefill", "bench.prefill")
        harness.wrap_annotated(engine, "_decode", "bench.decode")
        for _, store in engine.stores:
            harness.wrap_annotated(store, "gather_rows_host",
                                   "bench.gather_rows_host")

    reqs = gen.requests(mix, vocab, ctx.seed, ctx.seconds)
    batch = [Request(id=r.id, prompt=r.prompt,
                     max_new_tokens=r.max_new_tokens, arrival_s=r.due_s)
             for r in reqs]
    compiles0 = ctx.compiles.count
    trace, reports = {}, []
    ctx.window_start = time.perf_counter()
    with harness.profiled(ctx.trace_dir, trace):
        t0 = time.perf_counter()
        if mix["arrival"] == "poisson":
            reports.append(engine.run(batch))
        else:
            while time.perf_counter() - t0 < ctx.seconds:
                reports.append(engine.run(batch))
        elapsed = time.perf_counter() - t0
    window_compiles = ctx.compiles.count - compiles0
    peak = harness.memory_peak_bytes(ctx.devices)

    finished = [r for rep in reports for r in rep.requests]
    attempted = len(batch) * len(reports)
    due = {r.id: r.due_s for r in reqs}
    ttft, tpot, per_request = [], [], []
    for r in finished:
        first = r.admit_s + r.prefill_s
        ttft.append(first - due[r.id])
        if len(r.tokens) > 1:
            tpot.append((r.finish_s - first) / (len(r.tokens) - 1))
        per_request.append((r.prompt_len, len(r.tokens)))
    generated = sum(rep.generated_tokens for rep in reports)
    prefill_s = [x for rep in reports for x in rep.prefill_s]
    step_s = [x for rep in reports for x in rep.step_s]
    stats = {k: sum(rep.cache[k] for rep in reports if rep.cache)
             for k in ("hits", "misses", "uncached")}
    end_to_end = {"ttft_p95_ms": 1e3 * harness.quantile_p(ttft, 0.95),
                  "tpot_p95_ms": 1e3 * harness.quantile_p(tpot, 0.95),
                  "serve_tokens_per_s": generated / elapsed}
    harness.log(window={"requests": attempted, "finished": len(finished),
                        "replays": len(reports), "elapsed_s": elapsed,
                        "generated": generated,
                        "ttft_p50_ms": 1e3 * harness.quantile_p(ttft, 0.5),
                        "tpot_p50_ms": 1e3 * harness.quantile_p(tpot, 0.5),
                        "ticks": len(step_s),
                        "late_start_s": max(0.0, min(
                            (r.admit_s - due[r.id] for r in finished),
                            default=0.0))})

    picked = sample(finished, ctx.seed, wl["check_tokens"])
    length = max(len(r.prompt) + len(r.tokens) - 1 for r in picked)
    length = min(wl["max_len"], 1 << (length - 1).bit_length())
    del engine, params, reports, batch
    gc.collect()
    jax.clear_caches()
    got = reference_gaps(conf, picked, length)
    limit = wl["limits"]["served_logit_gap"]
    checks = [{"name": "served_logit_gap", "value": got["served_logit_gap"],
               "limit": limit, "ok": got["served_logit_gap"] <= limit}]
    return {
        "attempted": attempted, "failed": attempted - len(finished),
        "checks": checks, "window_compiles": window_compiles,
        "memory_peak_bytes": peak, "trace": trace,
        "end_to_end": end_to_end,
        "inputs": {"elapsed_s": elapsed, "prefill_s": prefill_s,
                   "step_s": step_s, "store_stats": stats,
                   "requests": per_request},
    }
