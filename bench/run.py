"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell is `BENCHMARK.json`'s workload of
that name; its driver, configuration, traffic and per-layer readers are
files under `bench/` found by name (see `bench/harness.py`).  The last
line of standard output is one JSON object:

    {"correct", "attempted", "failed", "metrics", "device", ["breakdown"],
     "checks"}

With `--trace 0` the metrics are the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics, read from a profile of the window.
The run exits non-zero, and prints no result, without an accelerator,
with fewer chips than the cell asks for, or without the program's
sources beside the benchmark.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def per_layer(cell: dict, metrics: list[dict], out: dict, reduced, peaks,
              device_kind: str) -> dict:
    """Each per-layer metric from its reader; a reader that finds nothing
    to read returns None and the metric is left out."""
    import flops

    ctx = {"cell": cell, "inputs": out["inputs"], "trace": reduced,
           "peak": peaks["devices"].get(device_kind), "flops": flops}
    got = {}
    for m in metrics:
        value = harness.reader(m["name"]).read(ctx)
        if value is not None:
            got[m["name"]] = {"value": value, "unit": m["unit"]}
    return got


def run_cell(args, *, cell: dict | None = None,
             chips_required: bool = True) -> dict:
    """Everything but the printing: the result dict and its checks.  Tests
    pass a smaller `cell` and `chips_required=False`."""
    bench = harness.benchmark()
    cell = cell or harness.cell(args.workload, bench)
    e2e, layer = harness.metrics_of(args.workload, bench)
    harness.program_path()
    import jax

    if chips_required:
        device = harness.require_chips(cell["entry"]["chips"])
    else:
        device = harness.describe(jax.devices()[: cell["entry"]["chips"]])
    devices = jax.devices()[: cell["entry"]["chips"]]
    peaks = harness.load_json(harness.BENCH / "peaks.json")
    if chips_required and device["kind"] not in peaks["devices"]:
        raise harness.BenchError(f"no peaks for {device['kind']!r} in "
                                 f"bench/peaks.json")
    cache = harness.use_compile_cache()
    ctx = types.SimpleNamespace(
        cell=cell, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), devices=devices,
        compiles=harness.CompileCounter(),
        trace_dir=harness.trace_dir() if args.trace else None,
        window_start=None)
    harness.log(cell=args.workload, seed=args.seed, compile_cache=cache,
                device=device)
    out = harness.driver(cell["workload"]).run(ctx)
    setup_s = ctx.window_start - T_START
    harness.log(setup_s=setup_s, window_compiles=out["window_compiles"])
    result = {"correct": all(c["ok"] for c in out["checks"]),
              "attempted": out["attempted"], "failed": out["failed"]}
    device = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    if args.trace:
        import trace_reduce

        tr = out["trace"]
        reduced = trace_reduce.reduce(tr["trace_file"], len(devices))
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["metrics"] = per_layer(cell, layer, out, reduced, peaks,
                                      device["kind"])
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        harness.log(op_classes=reduced["op_classes"],
                    host_spans=reduced["host_spans"])
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    else:
        values = dict(out["end_to_end"], setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]} for m in e2e}
    result["device"] = device
    return {"result": result, "checks": out["checks"]}


def main(argv=None) -> int:
    args = parse(argv)
    try:
        done = run_cell(args)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.emit(done["result"], done["checks"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
