"""Arithmetic the per-layer readers share.  Each reader is a file
`<metric>.py` with `read(ctx) -> float | None`; `ctx` holds the cell's
files (`cell`), what its driver measured (`inputs`), the reduced trace
(`trace`), the device's peaks (`peak`) and `bench/flops.py` (`flops`)."""

from __future__ import annotations


def idle_percent(ctx):
    tr = ctx["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def serve_mfu_percent(ctx):
    """Model FLOPs of every finished request's prompt and generated
    tokens over the window, as a share of the chip's bf16 peak."""
    inputs, peak = ctx["inputs"], ctx["peak"]
    reqs = inputs.get("requests")
    if not reqs or not peak:
        return None
    conf, fl = ctx["cell"]["config"], ctx["flops"]
    total = sum(fl.request_flops(conf, p, g) for p, g in reqs)
    return 100.0 * total / inputs["elapsed_s"] / peak["bf16_flops"]


def mean_ms(xs):
    return 1e3 * sum(xs) / len(xs) if xs else None


def hit_percent(ctx):
    st = ctx["inputs"].get("store_stats") or {}
    total = sum(st.get(k, 0) for k in ("hits", "misses", "uncached"))
    return 100.0 * st["hits"] / total if total else None
