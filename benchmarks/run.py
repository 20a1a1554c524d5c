"""Benchmark harness — one module per paper table.

    PYTHONPATH=src python -m benchmarks.run [table1 table3 ...] \
        [--smoke] [--json] [--out BENCH_ci.json]

Default output is ``name,us_per_call,derived`` CSV.  ``--json`` emits one
machine-readable *summary document* instead — the same schema
`repro.launch.serve --json` uses (top-level ``rows`` holding
``[name, us_per_call, derived]`` triples; `validate_summary` below is the
shared contract both emitters and `tools/check_bench.py` check against).
``--smoke`` asks each module for its reduced sweep (passed through to
``run(smoke=True)`` where the module supports it) — this is what the CI
``bench`` job runs before gating on `benchmarks/baseline.json`.

``--metrics-dir DIR`` arms the observability layer (`repro.obs`) for the
whole sweep: instrumented layers (serving engine, tiered store, lifecycle
controller, table5's utilisation gauges) stream to ``DIR/metrics.jsonl``
and a Prometheus textfile snapshot, and the summary document carries the
final registry snapshot under its ``metrics`` key (``repro.obs.v1`` —
`tools/check_bench.py` gates on it).

Each module exposes ``run() -> list[(name, us_per_call, derived)]``.
"""

from __future__ import annotations

import argparse
import inspect
import json
import math
import sys
import time
import traceback

MODULES = ("table1_lattice", "table2_lm", "table3_opcounts",
           "table4_timing", "table5_utilisation", "table6_tiering",
           "table7_quant", "table8_serving", "table9_backends",
           "table10_lifecycle")


def validate_summary(doc) -> None:
    """Assert `doc` is a benchmark summary document.

    The shared schema (emitted by both ``benchmarks.run --json`` and
    ``repro.launch.serve --json``): a JSON object whose ``rows`` key holds
    a list of ``[name, us_per_call, derived]`` triples — name a non-empty
    string, us_per_call a finite non-negative number, derived a string.
    Extra keys are allowed (each emitter adds its own detail fields).
    Raises ValueError on the first violation.
    """
    if not isinstance(doc, dict):
        raise ValueError(f"summary must be an object, got {type(doc)}")
    rows = doc.get("rows")
    if not isinstance(rows, list) or not rows:
        raise ValueError("summary must carry a non-empty 'rows' list")
    for i, row in enumerate(rows):
        if not (isinstance(row, (list, tuple)) and len(row) == 3):
            raise ValueError(f"rows[{i}]: expected [name, us, derived]")
        name, us, derived = row
        if not isinstance(name, str) or not name:
            raise ValueError(f"rows[{i}]: name must be a non-empty string")
        if (isinstance(us, bool) or not isinstance(us, (int, float))
                or not math.isfinite(us) or us < 0):
            raise ValueError(
                f"rows[{i}] ({name}): us_per_call must be a finite "
                f"non-negative number, got {us!r}"
            )
        if not isinstance(derived, str):
            raise ValueError(f"rows[{i}] ({name}): derived must be a string")
    if "metrics" in doc:
        from repro.obs import export as obs_export
        try:
            obs_export.validate_metrics_doc(doc["metrics"])
        except ValueError as e:
            raise ValueError(f"summary 'metrics' doc invalid: {e}") from e


def collect(tables: list[str], *, smoke: bool = False):
    """Run the selected modules; returns (rows, failures)."""
    selected = set(a.split("_")[0] for a in tables)
    rows: list[tuple[str, float, str]] = []
    failures = 0
    for mod_name in MODULES:
        if selected and mod_name.split("_")[0] not in selected:
            continue
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            kwargs = {}
            if smoke and "smoke" in inspect.signature(mod.run).parameters:
                kwargs["smoke"] = True
            rows.extend((name, us, derived)
                        for name, us, derived in mod.run(**kwargs))
        except Exception as e:
            failures += 1
            rows.append((f"{mod_name}.ERROR", 0.0, f"{type(e).__name__}: {e}"))
            traceback.print_exc(file=sys.stderr)
        print(f"# {mod_name} took {time.time()-t0:.1f}s", file=sys.stderr)
    return rows, failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("tables", nargs="*",
                    help="table selections (e.g. table1 table6); "
                         "default: all")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sweeps (modules that support smoke=True)")
    ap.add_argument("--json", action="store_true",
                    help="emit the summary document instead of CSV")
    ap.add_argument("--out", default=None, metavar="FILE",
                    help="also write the summary document to FILE "
                         "(e.g. BENCH_ci.json; implies the JSON schema)")
    ap.add_argument("--metrics-dir", default="", metavar="DIR",
                    help="arm repro.obs for the sweep: JSONL event log + "
                         "Prometheus textfile in DIR, registry snapshot "
                         "in the summary's 'metrics' key")
    args = ap.parse_args(argv)

    from repro import obs
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    if args.metrics_dir:
        obs.configure(metrics_dir=args.metrics_dir)
    rows, failures = collect(args.tables, smoke=args.smoke)
    if args.metrics_dir:
        obs.flush()
    doc = {
        "rows": [[name, us, derived] for name, us, derived in rows],
        "tables": args.tables or list(MODULES),
        "smoke": args.smoke,
        "failures": failures,
        "metrics": obs.metrics_doc(),
    }
    validate_summary(doc)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=2)
        print(f"# wrote {args.out}", file=sys.stderr)
    if args.json:
        print(json.dumps(doc))
    else:
        print("name,us_per_call,derived")
        for name, us, derived in rows:
            print(f"{name},{us:.3f},{derived}")
    if failures:
        print(f"{failures} benchmark modules failed", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
