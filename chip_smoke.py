"""Bring-up check on a TPU: drive the main paths once, at full width.

    python chip_smoke.py             # one chip: kernels, train, serve
    python chip_smoke.py --chips 4   # four chips: the row-sharded train
                                     # step against the one-device step

Everything runs in this one process (a chip belongs to one process), and
through the entry points a user calls: `repro.launch.train.main` and
`repro.launch.serve.main`.  Each phase prints one JSON line with its result
and wall time; a phase that fails raises, so the script exits non-zero and
prints no last line.  The last line of a passing run is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}

With no TPU (or without the repository's `src/` beside it) the script
exits non-zero before any phase.

Phases (one chip):
  kernels  the Pallas gathers — dense fp32 and int8, tiered fp32 and int8
           on a warmed cache — at the full table (2^20 x 64, top_k=32),
           forward and grad against the jnp references (`kernels/ref.py`);
           the compiled text must hold a Mosaic kernel (`tpu_custom_call`)
  train    `train.main` on lram-bert-medium (w=512, 6 layers, 2^20 rows),
           then one jitted loss+grad step with the reference and with the
           Pallas gather on the same batch
  serve    `serve.main` on lram-tiered's full config (w=512, vocab 30000,
           2^20 rows, 32 of 128 shards resident), then one request's
           logits against `transformer.forward` over the same tokens
Four chips (`--chips 4`), and nothing else:
  sharded  one lram-bert-large train step (2^22 rows) with the table
           row-sharded over `model` on a 2x2 (data, model) mesh, through
           `train.main --use-mesh --placement sharded`, against the same
           step on one device
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# Agreement bounds, fixed before any chip run.
# fp32 gathers: |pallas - reference| <= F32_GATHER_RTOL * max_t sum_k |w|
# * max|row|, the reference computed at full f32 matmul precision.
F32_GATHER_RTOL = 1e-5
# Quantized gathers against the fp32 reference: quant.max_abs_error_bound.
# Pallas vs reference train step, both at full f32 matmul precision:
STEP_LOSS_RTOL = 1e-5    # |loss_p - loss_r| <= this * max(1, |loss_r|)
STEP_GRAD_RTOL = 1e-4    # max|g_p - g_r| over the table <= this * max|g_r|
# Decode logits against transformer.forward over the same tokens:
LOGIT_RTOL_F32 = 1e-4    # both at full f32 precision, of max|logits|
LOGIT_RTOL_DEFAULT = 1e-2  # engine's prefill at the default precision
# Row-sharded vs one-device train step (full f32 precision):
SHARDED_LOSS_RTOL = 1e-4
SHARDED_GRAD_NORM_RTOL = 1e-3
SHARDED_PARAM_ATOL = 5e-3  # as tests/test_distributed.py


def _phase(name, fn):
    t0 = time.perf_counter()
    result = fn()
    print(json.dumps({"phase": name, "ok": True,
                      "seconds": time.perf_counter() - t0, **result}),
          flush=True)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def _require_kernel(compiled) -> None:
    """A compiled program that must hold a Mosaic kernel (so interpret
    mode cannot pass for the chip)."""
    _check("tpu_custom_call" in compiled.as_text(),
           "no tpu_custom_call in the compiled text: the kernel did not "
           "compile to Mosaic")


def _compiled(fn, *args):
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    _require_kernel(compiled)
    return compiled


def _max_abs(a, b) -> float:
    import numpy as np

    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


def _table_leaf(tree):
    """The lram value table (or its gradient) in a params-shaped tree."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in flat:
        if jax.tree_util.keystr(path).endswith("['lram']['values']"):
            return leaf
    raise KeyError("no lram/values leaf")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_kernels(log2_rows=20, m=64, top_k=32, tokens=8192,
                  shard_rows=8192, cache_slots=32, seed=0):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import quant
    from repro.kernels import gather_interp, interpret_mode, ref, tiered_gather
    from repro.memstore import TieredSpec, TieredValueStore

    interpret = interpret_mode()  # False on a TPU
    rows = 2**log2_rows
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    values = 0.02 * jax.random.normal(k1, (rows, m), jnp.float32)
    idx = jax.random.randint(k2, (tokens, top_k), 0, rows, jnp.int32)
    w = jax.random.uniform(k3, (tokens, top_k), jnp.float32)
    probe = jax.random.normal(k4, (tokens, m), jnp.float32)
    f32_bound = F32_GATHER_RTOL \
        * float(np.max(np.sum(np.abs(np.asarray(w)), -1))) \
        * float(jnp.max(jnp.abs(values)))
    table = quant.QuantizedTable.from_dense(values, "int8")
    q_bound = quant.max_abs_error_bound(np.asarray(table.scale),
                                        np.asarray(w), "int8")

    def pallas_fp32(v, i, ww):
        return gather_interp.gather_interp_vjp(v, i, ww, interpret)

    def pallas_int8(ww, q, scale):
        return gather_interp.gather_interp_quant(q, scale, idx, ww,
                                                 interpret)

    def grads(fn, *args, wrt=2):
        """Grads of sum(fn(*args) * probe) in the first `wrt` args (the
        tables ride as arguments, not as constants baked into the
        program)."""
        return jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a) * probe),
                                argnums=tuple(range(wrt))))(*args)

    out: dict = {}
    # references and the backward passes' XLA einsums at full f32 precision
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref.gather_interp_ref)(values, idx, w)

        # dense fp32: forward, and grad through the differentiable wrapper
        got = _compiled(pallas_fp32, values, idx, w)(values, idx, w)
        err = _max_abs(got, want)
        _check(err <= f32_bound, f"dense fp32 err {err} > {f32_bound}")
        g_want = grads(lambda v, ww: ref.gather_interp_ref(v, idx, ww),
                       values, w)
        g_got = grads(lambda v, ww: pallas_fp32(v, idx, ww), values, w)
        g_err = max(_max_abs(a, b) for a, b in zip(g_got, g_want))
        g_bound = F32_GATHER_RTOL * max(float(jnp.max(jnp.abs(g)))
                                        for g in g_want)
        _check(g_err <= g_bound, f"dense fp32 grad err {g_err} > {g_bound}")
        out["dense_fp32"] = {"err": err, "bound": f32_bound,
                             "grad_err": g_err}

        # dense int8: within the int8 bound of the fp32 reference, and
        # its dw equal to the quantized reference's
        q_args = (w, table.q, table.scale)
        got = _compiled(pallas_int8, *q_args)(*q_args)
        err = _max_abs(got, want)
        _check(err <= q_bound, f"dense int8 err {err} > {q_bound}")
        (g_q,) = grads(pallas_int8, *q_args, wrt=1)
        (g_q_ref,) = grads(
            lambda ww, q, scale: quant.gather_interp_quant(
                quant.QuantizedTable(q, scale, "int8"), idx, ww),
            *q_args, wrt=1)
        g_err = _max_abs(g_q, g_q_ref)
        g_bound = F32_GATHER_RTOL * float(jnp.max(jnp.abs(g_q_ref)))
        _check(g_err <= g_bound, f"dense int8 grad err {g_err} > {g_bound}")
        out["dense_int8"] = {"err": err, "bound": q_bound, "grad_err": g_err}

        # tiered: warmed cache, indices in resident shards, so the store
        # takes the kernel path
        host_values = np.asarray(values)
        for kind in ("none", "int8"):
            spec = TieredSpec(shard_rows=shard_rows, cache_slots=cache_slots,
                              use_pallas=True, quant=kind)
            store = TieredValueStore.from_dense(host_values, spec)
            store.warm()
            resident = np.asarray(store.resident_shards(), np.int64)
            r_idx = (resident[np.asarray(idx) % len(resident)] * shard_rows
                     + np.asarray(idx) % shard_rows).astype(np.int32)
            r_want = jax.jit(ref.gather_interp_ref)(values, r_idx, w)
            store.reset_stats()
            got = store.gather(r_idx, w)
            _check(store.stats["misses"] == 0
                   and store.stats["uncached"] == 0,
                   f"tiered {kind}: the warmed cache missed")
            args = [store.cache_dev.reshape(-1, m), r_idx,
                    jnp.asarray(store._shard_slot), w]
            if kind == "none":
                kernel = functools.partial(tiered_gather.tiered_gather_pallas,
                                           shard_rows=shard_rows,
                                           interpret=interpret)
                bound = f32_bound
            else:
                args.insert(1, store.cache_scale_dev.reshape(-1))
                kernel = functools.partial(
                    tiered_gather.tiered_gather_quant_pallas,
                    shard_rows=shard_rows, interpret=interpret)
                bound = q_bound
            direct = _compiled(kernel, *args)(*args)
            err = _max_abs(got, r_want)
            _check(err <= bound, f"tiered {kind} err {err} > {bound}")
            _check(_max_abs(direct, got) <= f32_bound,
                   f"tiered {kind}: store.gather is not the kernel")
            name = "tiered_fp32" if kind == "none" else "tiered_int8"
            out[name] = {"err": err, "bound": bound}
    return out


def phase_train(arch="lram-bert-medium", steps=4, batch=8, seq=128,
                smoke=False):
    import jax
    import numpy as np

    from repro import configs, data
    from repro.launch import train
    from repro.models import transformer

    argv = ["--arch", arch, "--steps", str(steps), "--batch", str(batch),
            "--seq", str(seq), "--log-every", "1"]
    _, history = train.main(argv + (["--smoke"] if smoke else []))
    _check(len(history) == steps, f"{len(history)} logged steps")
    for rec in history:
        _check(np.isfinite(rec["loss"]) and np.isfinite(rec["grad_norm"]),
               f"non-finite step {rec}")

    # one jitted loss+grad step, reference vs Pallas gather, same batch
    base = configs.get_smoke_config(arch) if smoke else configs.get_config(arch)
    dcfg = data.DataConfig(vocab_size=base.vocab_size, seq_len=seq,
                           global_batch=batch, objective=base.objective)
    b = jax.tree.map(jax.numpy.asarray, data.get_batch(dcfg, step=0))
    res = {}
    for impl in ("reference", "pallas"):
        cfg = configs.with_placement(base, impl)
        params, state = transformer.init(jax.random.PRNGKey(0), cfg)

        def step(p, s, bb, cfg=cfg):
            return jax.value_and_grad(transformer.loss_fn, has_aux=True)(
                p, s, bb, cfg, train=True)

        with jax.default_matmul_precision("highest"):
            lowered = jax.jit(step).lower(params, state, b)
        compiled = lowered.compile()
        if impl == "pallas":
            _require_kernel(compiled)
        (loss, _), grads = compiled(params, state, b)
        res[impl] = (float(loss), np.asarray(_table_leaf(grads)))
        del params, grads
    (loss_r, g_r), (loss_p, g_p) = res["reference"], res["pallas"]
    loss_err = abs(loss_p - loss_r)
    grad_err = _max_abs(g_p, g_r)
    grad_bound = STEP_GRAD_RTOL * float(np.max(np.abs(g_r)))
    _check(loss_err <= STEP_LOSS_RTOL * max(1.0, abs(loss_r)),
           f"pallas vs reference loss {loss_p} vs {loss_r}")
    _check(grad_err <= grad_bound,
           f"pallas vs reference table grad err {grad_err} > {grad_bound}")
    return {"arch": arch, "losses": [r["loss"] for r in history],
            "grad_norms": [r["grad_norm"] for r in history],
            "step_loss": {"reference": loss_r, "pallas": loss_p},
            "table_grad_err": grad_err, "table_grad_bound": grad_bound}


def phase_serve(arch="lram-tiered", slots=4, requests=8, prompt_len=64,
                gen=16, smoke=False):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import configs
    from repro.launch import serve
    from repro.models import transformer

    argv = ["--arch", arch, "--batch", str(slots), "--requests",
            str(requests), "--prompt-len", str(prompt_len), "--gen",
            str(gen), "--fixed-len", "--json"]
    report = serve.main(argv + (["--smoke"] if smoke else []))
    _check(len(report.requests) == requests,
           f"{len(report.requests)} of {requests} requests finished")
    for r in report.requests:
        _check(len(r.tokens) == gen and r.decode_steps == gen - 1,
               f"request {r.id}: {len(r.tokens)} of {gen} tokens")

    # one request: its decode, teacher-forced with the tokens the engine
    # generated, against a full forward over the same tokens
    cfg = configs.get_smoke_config(arch) if smoke else configs.get_config(arch)
    params, state = transformer.init(jax.random.PRNGKey(0), cfg)  # serve's
    req = report.requests[0]
    seq = np.concatenate([req.prompt, req.tokens[:-1]]).astype(np.int32)
    # params ride as arguments, not as constants baked into the programs
    fwd = jax.jit(lambda p, t: transformer.forward(
        p, state, {"tokens": t}, cfg)[0])
    tokens = jnp.asarray(seq[None])
    ref_default = np.asarray(fwd(params, tokens))[0]
    scale = max(1.0, float(np.max(np.abs(ref_default))))
    first_err = _max_abs(req.first_logits, ref_default[prompt_len - 1])
    _check(first_err <= LOGIT_RTOL_DEFAULT * scale,
           f"engine first logits err {first_err}")
    max_len = prompt_len + gen
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(lambda p, t: transformer.forward(
            p, state, {"tokens": t}, cfg)[0])(params, tokens))[0]
        pre = jax.jit(lambda p, t: transformer.prefill(
            p, state, {"tokens": t}, cfg, max_len))
        dec = jax.jit(lambda p, t, pos, c: transformer.decode_step(
            p, state, t, pos, c, cfg))
        logits, cache = pre(params,
                            jnp.asarray(req.prompt[None].astype(np.int32)))
        got = [np.asarray(logits)[0, -1]]
        for i, tok in enumerate(req.tokens[:-1]):
            logits, cache = dec(params, jnp.asarray([[tok]], jnp.int32),
                                jnp.int32(prompt_len + i), cache)
            got.append(np.asarray(logits)[0, -1])
    err = _max_abs(np.stack(got), ref[prompt_len - 1:])
    bound = LOGIT_RTOL_F32 * max(1.0, float(np.max(np.abs(ref))))
    _check(err <= bound, f"decode vs forward logits err {err} > {bound}")
    return {"arch": arch, "requests": len(report.requests),
            "generated_tokens": report.generated_tokens,
            "cache": report.cache, "first_logits_err": first_err,
            "decode_logits_err": err, "decode_logits_bound": bound}


def phase_sharded(arch="lram-bert-large", batch=8, seq=128, smoke=False):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import configs, data, optim
    from repro.launch import train
    from repro.models import transformer

    argv = ["--arch", arch, "--steps", "1", "--batch", str(batch), "--seq",
            str(seq), "--use-mesh", "--placement", "sharded",
            "--log-every", "1"] + (["--smoke"] if smoke else [])
    with jax.default_matmul_precision("highest"):
        p_mesh, history = train.main(argv)
        # the same step on one device: dense table, same init and batch
        cfg = (configs.get_smoke_config(arch) if smoke
               else configs.get_config(arch))
        dcfg = data.DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                               global_batch=batch, objective=cfg.objective,
                               seed=0)  # train.main's --seed
        b = jax.tree.map(jnp.asarray, data.get_batch(dcfg, step=0))
        params, state = transformer.init(jax.random.PRNGKey(0), cfg)
        step = train.build_train_step(cfg, optim.OptimConfig())
        p_one, _, _, _, m_one = step(params, optim.adam_init(params), state,
                                     jnp.zeros(()), b)
    loss_m, loss_1 = history[0]["loss"], float(m_one["loss"])
    gn_m, gn_1 = history[0]["grad_norm"], float(m_one["grad_norm"])
    _check(abs(loss_m - loss_1) <= SHARDED_LOSS_RTOL * max(1.0, abs(loss_1)),
           f"sharded loss {loss_m} vs one device {loss_1}")
    _check(abs(gn_m - gn_1) <= SHARDED_GRAD_NORM_RTOL * gn_1,
           f"sharded grad_norm {gn_m} vs one device {gn_1}")
    diffs = jax.tree.map(_max_abs, p_mesh, p_one)
    worst = max(jax.tree.leaves(diffs))
    _check(worst <= SHARDED_PARAM_ATOL,
           f"updated params differ by {worst}")
    table = _table_leaf(p_mesh)
    return {"arch": arch, "mesh": dict(table.sharding.mesh.shape),
            "table_spec": str(table.sharding.spec),
            "loss": {"sharded": loss_m, "one_device": loss_1},
            "grad_norm": {"sharded": gn_m, "one_device": gn_1},
            "table_update_err": _max_abs(table, _table_leaf(p_one)),
            "params_max_err": worst}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: kernels, train, serve; 4: the row-sharded "
                         "train step against one device, nothing else")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repository sources at {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    from repro.launch.cache import use_compile_cache

    print(json.dumps({"compile_cache": use_compile_cache()}), flush=True)
    if args.chips == 4:
        _phase("sharded", phase_sharded)
    else:
        _phase("kernels", phase_kernels)
        _phase("train", phase_train)
        _phase("serve", phase_serve)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
