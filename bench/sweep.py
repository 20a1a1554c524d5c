"""Find the load a serving cell's traffic file states: one engine, each
cell's traffic at each swept load in turn, one process, on the chip.

    python3 bench/sweep.py --cell serve-tiered-chat serve-tiered-offline \
        --rates 0.4 0.6 0.8 --counts 16 32 --seconds 51 --seed 1

A Poisson mix is swept over `--rates`: for each rate one JSON line with
TTFT and TPOT quantiles and the number of requests waiting for a slot (due,
not yet admitted) at each quarter of the window.  The knee is the highest
rate at which that number does not grow over the window (at the end no
more than at the half, or 1), at every rate up to it; the traffic file
states about four fifths of it.  A batch mix is swept over `--counts`: the
wall time of one replay of that many requests; the traffic file states the
count whose replay takes about a third of `--seconds`.  The last line
holds both picks.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def waiting(finished, due: dict, t: float) -> int:
    """Requests due by t and not admitted by t."""
    return sum(1 for r in finished if due[r.id] <= t < r.admit_s)


def knee(lines: list[dict]) -> float | None:
    """The highest rate up to which no swept rate's queue grew."""
    best = None
    for line in sorted(lines, key=lambda x: x["rate"]):
        w = line["waiting"]
        if w[-1] > max(1, w[1]):
            break
        best = line["rate"]
    return best


def count_for(lines: list[dict], target_s: float) -> int:
    """The count whose replay takes about `target_s`, scaled from the
    swept count whose replay came closest to it."""
    near = min(lines, key=lambda x: abs(x["wall_s"] - target_s))
    return max(1, round(near["count"] * target_s / near["wall_s"]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", nargs="+", required=True)
    ap.add_argument("--rates", type=float, nargs="*", default=[])
    ap.add_argument("--counts", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    harness.program_path()
    harness.use_compile_cache()
    cells = [harness.cell(name) for name in args.cell]
    drv = harness.driver(cells[0]["workload"])
    _, weights, gen = drv._imports()
    from repro.serving import EngineConfig, Request, ServeEngine

    conf, wl = cells[0]["config"], cells[0]["workload"]
    if any(c["config"] != conf for c in cells):
        raise SystemExit("cells swept together share a configuration")
    pcfg = harness.program_config(conf)
    params, state = weights.make(conf, conf["weight_seed"])
    engine = ServeEngine(drv.place_table(params, pcfg), state, pcfg,
                         EngineConfig(slots=wl["slots"], max_len=wl["max_len"],
                                      mode=wl["mode"]))
    lo = min(gen.length_range(c["traffic"])[0] for c in cells)
    hi = max(gen.length_range(c["traffic"])[1] for c in cells)
    engine.run([Request(id=i, prompt=[1] * n, max_new_tokens=2) for i, n in
                enumerate(drv.warm_lengths(lo, hi, wl["max_len"]))])
    drv.warm_cache_sync(engine)
    q = harness.quantile_p
    picks = {}
    for cell in cells:
        mix = cell["traffic"]
        poisson = mix["arrival"] == "poisson"
        lines = []
        for load in (args.rates if poisson else args.counts):
            m = dict(mix, rate=load) if poisson else dict(mix, count=load)
            reqs = gen.requests(m, conf["vocab_size"], args.seed, args.seconds)
            report = engine.run([Request(id=r.id, prompt=r.prompt,
                                         max_new_tokens=r.max_new_tokens,
                                         arrival_s=r.due_s) for r in reqs])
            due = {r.id: r.due_s for r in reqs}
            ttft = [r.admit_s + r.prefill_s - due[r.id]
                    for r in report.requests]
            tpot = [(r.finish_s - r.admit_s - r.prefill_s)
                    / (len(r.tokens) - 1)
                    for r in report.requests if len(r.tokens) > 1]
            line = {
                "cell": cell["name"], ("rate" if poisson else "count"): load,
                "requests": len(reqs), "finished": len(report.requests),
                "wall_s": report.wall_s,
                "ttft_ms": {p: 1e3 * q(ttft, p) for p in (0.5, 0.95)},
                "tpot_ms": {p: 1e3 * q(tpot, p) for p in (0.5, 0.95)},
                "tokens_per_s": report.tokens_per_sec,
                "waiting": [waiting(report.requests, due, f * args.seconds)
                            for f in (0.25, 0.5, 0.75, 1.0)],
                "tick_ms": 1e3 * sum(report.step_s) / max(1, len(
                    report.step_s)),
                "prefill_ms": 1e3 * sum(report.prefill_s) / max(1, len(
                    report.prefill_s)),
                "hit_rate": report.cache and report.cache["hit_rate"],
            }
            lines.append(line)
            print(json.dumps(line), flush=True)
        if not lines:
            continue
        if poisson:
            k = knee(lines)
            picks[cell["name"]] = {"knee": k, "rate": k and round(0.8 * k, 3)}
        else:
            picks[cell["name"]] = {"count": count_for(lines,
                                                      args.seconds / 3)}
    print(json.dumps({"picks": picks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
