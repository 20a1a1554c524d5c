"""Table 9 (beyond-paper): the lookup-backend plan matrix.

Sweeps the cells of `repro.core.lookup`'s placement × storage × kernel
registry over one shared table draw and a drifting-hot-set access stream
(table6/7's decode-like pattern), reporting per-lookup latency and the max
abs output delta vs the dense fp32 reference — which must sit inside the
documented `repro.quant.max_abs_error_bound` for quantized storages and
float rounding for fp32.

    PYTHONPATH=src python -m benchmarks.run table9 --smoke   # harness rows
    PYTHONPATH=src python -m benchmarks.table9_backends

Sharded placements run under an in-process 1-device mesh with a `model`
axis — the layout/communication structure is exercised (shard_map + psum /
per-range routing), while multi-device equivalence lives in the slow
subprocess tests.  The smoke sweep times the reference-kernel cells
(tracked in `benchmarks/baseline.json`, gated at 1.3x by
`tools/check_bench.py` like every other hot path — sharded-tiered
included); the full sweep adds the Pallas cells, which run in interpret
mode on CPU and are timed with a reduced stream.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import quant
from repro.core import lookup, lram
from repro.distributed import context as _ctx
from repro.kernels import interpret_mode
from repro.launch.mesh import make_mesh
from repro.memstore import TieredSpec

M = 64
TOP_K = 32


def _params(smoke: bool):
    if smoke:
        # steps chosen for stable medians: the store-backed cells are
        # host-routing heavy and their per-call times jitter more than
        # the pure device gathers the gate calibrates on
        return dict(num_rows=2**14, shard_rows=512, batch=128,
                    steps=10, warmup=3)
    return dict(num_rows=2**16, shard_rows=2048, batch=256,
                steps=10, warmup=3)


def _cells(smoke: bool):
    ref_cells = [
        ("dense", "fp32", "reference"),
        ("dense", "int8", "reference"),
        ("tiered", "fp32", "reference"),
        ("tiered", "int8", "reference"),
        ("sharded", "fp32", "reference"),
        ("sharded-tiered", "fp32", "reference"),
        ("sharded-tiered", "int8", "reference"),
    ]
    if smoke:
        return ref_cells
    full = [(p, s, k)
            for p in lookup.PLACEMENTS
            for s in lookup.STORAGES
            for k in lookup.KERNELS]
    return ref_cells + [c for c in full if c not in ref_cells]


def _make_cfg(placement, storage, kernel, p):
    log2 = int(np.log2(p["num_rows"]))
    kw = dict(
        log2_locations=log2, m=M, heads=4, query_norm="rms",
        table_quant="none" if storage == "fp32" else storage,
        lookup_kernel=kernel,
    )
    num_shards = p["num_rows"] // p["shard_rows"]
    slots = max(2, num_shards // 4)  # 25% resident: fills on the clock
    if placement == "dense":
        return lram.LRAMConfig(interp_impl="reference", **kw)
    if placement == "tiered":
        return lram.LRAMConfig(
            interp_impl="tiered",
            tiered=TieredSpec(shard_rows=p["shard_rows"], cache_slots=slots),
            **kw,
        )
    if placement == "sharded":
        return lram.LRAMConfig(interp_impl="sharded", **kw)
    return lram.LRAMConfig(
        interp_impl="sharded-tiered", model_shards=2,
        tiered=TieredSpec(shard_rows=p["shard_rows"],
                          cache_slots=max(1, slots // 2)),
        **kw,
    )


def _stream(rng, steps, num_rows, batch):
    """table6's decode-like pattern: a drifting hot window so tiered fills
    stay on the clock while hits dominate."""
    hot_span = num_rows // 8
    center = 0
    for _ in range(steps):
        center = (center + rng.integers(0, num_rows // 16)) % num_rows
        yield ((center + rng.integers(0, hot_span, (batch, TOP_K)))
               % num_rows).astype(np.int32)


def _time_cell(interp_fn, rng, p, *, steps=None):
    times = []
    steps = p["steps"] if steps is None else steps
    for t, idx in enumerate(_stream(rng, steps, p["num_rows"], p["batch"])):
        w = rng.normal(size=idx.shape).astype(np.float32) / TOP_K
        t0 = time.perf_counter()
        out = interp_fn(idx, w)
        jax.block_until_ready(out)
        if t >= min(p["warmup"], steps - 1):
            times.append(time.perf_counter() - t0)
    return 1e6 * float(np.median(times))


def _accuracy(plan, table, dense, rng, p, storage):
    idx = rng.integers(0, p["num_rows"], size=(64, TOP_K)).astype(np.int32)
    w = rng.normal(size=idx.shape).astype(np.float32) / TOP_K
    want = np.einsum("...k,...km->...m", w, dense[idx])
    got = np.asarray(plan.interp(table, jnp.asarray(idx), jnp.asarray(w)))
    err = float(np.abs(got - want).max())
    if storage == "fp32":
        bound = 1e-4
    else:
        _, scale = quant.quantize_rows_np(dense, storage)
        bound = quant.max_abs_error_bound(scale, w, storage)
    return err, bound


def measure(smoke: bool = False):
    p = _params(smoke)
    rng = np.random.default_rng(0)
    dense = rng.normal(size=(p["num_rows"], M)).astype(np.float32) * 0.02
    dense_dev = jnp.asarray(dense)
    mesh = make_mesh((1,), ("model",))
    rows = []
    for placement, storage, kernel in _cells(smoke):
        cfg = _make_cfg(placement, storage, kernel, p)
        if placement == "sharded":
            _ctx.set_mesh(mesh)
        try:
            plan = lookup.resolve(cfg)
            table = plan.build_table(dense_dev)
            eager = plan.supports_prefetch  # store-backed: host cache walk
            if eager:
                if hasattr(table, "warm"):
                    table.warm()

                def fn(idx, w, _t=table, _pl=plan):
                    return _pl.interp(_t, idx, w)
            else:
                jitted = jax.jit(
                    lambda i, w, _t=table, _pl=plan: _pl.interp(_t, i, w)
                )

                def fn(idx, w, _j=jitted):
                    return _j(jnp.asarray(idx), jnp.asarray(w))

            # pallas cells run in interpret mode on CPU: tiny stream.
            # jitted device cells are sub-ms dispatch-dominated calls —
            # give them 3x the samples so the median rides out scheduler
            # jitter (they are gated at 1.3x in CI)
            if kernel == "pallas" and interpret_mode():
                steps = 3
            elif not eager:
                steps = 3 * p["steps"]
            else:
                steps = None
            us = _time_cell(fn, np.random.default_rng(1), p, steps=steps)
            err, bound = _accuracy(plan, table, dense,
                                   np.random.default_rng(2), p, storage)
            assert err <= bound + 1e-6, (
                f"{plan.cell}: err {err:.3e} exceeds bound {bound:.3e}"
            )
            derived = f"err={err:.2e} bound={bound:.2e}"
            if hasattr(table, "hit_rate"):
                derived += f" hit={table.hit_rate():.3f}"
        finally:
            if placement == "sharded":
                _ctx.set_mesh(None)
        name = f"backend_{placement}_{storage}_{kernel}".replace("-", "_")
        rows.append((name, us, derived))
    return rows


def run(smoke: bool = False):
    return measure(smoke=smoke)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    for name, us, derived in run(smoke=args.smoke):
        print(f"{name},{us:.3f},{derived}")


if __name__ == "__main__":
    main()
