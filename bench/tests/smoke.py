"""Small same-family stand-ins for the cells, for the CPU tests: the
program's smoke configurations (`get_smoke_config`), stated in the same
file format, with traffic cut to match."""

from __future__ import annotations

import copy
import sys
import types
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

harness.program_path()

SMOKE_MODEL = {"d_model": 64, "num_heads": 4, "num_kv_heads": 4,
               "head_dim": 16, "d_ff": 128, "vocab_size": 256, "smoke": True}


def smoke_config(name: str) -> dict:
    conf = copy.deepcopy(harness.load_json(BENCH / "configs" /
                                           f"{name}.json"))
    conf.update(SMOKE_MODEL)
    conf["lram"].update({"log2_locations": 16, "heads": 4,
                         "torus_K": [8] * 8})
    if conf["arch"].startswith("lram-bert"):
        conf.update({"num_layers": 3, "memory_layer": 1, "max_seq": 64,
                     "head_dim": 8})
    else:
        conf.update({"num_layers": 2, "memory_layer": 1})
        conf["lram"]["tiered"].update({"shard_rows": 2048, "cache_slots": 8})
    return conf


# Cells whose files are here but which BENCHMARK.json does not hold yet
# (PERF.md, Open questions): their entries, for the CPU rehearsal.
PENDING = {
    "serve-tiered-chat": {"config": "lram-tiered", "traffic": "chat-poisson",
                          "chips": 1},
    "serve-tiered-offline": {"config": "lram-tiered",
                             "traffic": "offline-batch", "chips": 1},
}
PENDING_CONFIGS = {"lram-tiered": "bench/configs/lram-tiered.json"}


def full_cell(name: str) -> dict:
    """The cell `name` at its own size: BENCHMARK.json's, or a pending
    one's."""
    bench = harness.benchmark()
    if name in PENDING and name not in {w["name"] for w in
                                        bench["workloads"]}:
        bench["workloads"].append(dict(PENDING[name], name=name))
        have = {c["name"] for c in bench["configs"]}
        bench["configs"] += [{"name": n, "file": f} for n, f in
                             PENDING_CONFIGS.items() if n not in have]
    return harness.cell(name, bench)


def smoke_cell(name: str) -> dict:
    """The cell `name` with its configuration, traffic and engine cut to
    the smoke size."""
    cell = full_cell(name)
    cell["config"] = smoke_config(cell["config"]["name"])
    mix = cell["traffic"]
    if mix["kind"] == "mlm":
        mix.update({"batch": 4, "seq_len": 32})
    else:
        mix["prompt"].update({"median": 8, "min": 4, "max": 40})
        mix["output"].update({"median": 4, "min": 2, "max": 16})
        if mix["arrival"] == "poisson":
            mix["rate"] = 20.0
        else:
            mix["count"] = 6
        cell["workload"].update({"slots": 4, "max_len": 64,
                                 "check_tokens": 24})
    return cell


def context(cell: dict, *, seed: int = 7, seconds: float = 1.0,
            trace: bool = False, trace_dir=None):
    import jax

    return types.SimpleNamespace(
        cell=cell, seed=seed, seconds=seconds, trace=trace,
        devices=jax.devices()[:1], compiles=harness.CompileCounter(),
        trace_dir=trace_dir, window_start=None)
