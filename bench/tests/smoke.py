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


SMOKE_LRAM = {"log2_locations": 16, "torus_K": [8] * 8}


def smoke_config(name: str) -> dict:
    """The configuration file `name` at the smoke size."""
    return smoke_size(harness.load_json(BENCH / "configs" / f"{name}.json"))


def smoke_size(conf: dict) -> dict:
    """A copy of the configuration `conf` at the smoke size."""
    conf = copy.deepcopy(conf)
    if not conf["arch"].startswith("lram-"):
        return _inserted_smoke_config(conf)
    conf.update(SMOKE_MODEL)
    conf["lram"].update(SMOKE_LRAM, heads=4)
    if conf["arch"].startswith("lram-bert"):
        conf.update({"num_layers": 3, "memory_layer": 1, "max_seq": 64,
                     "head_dim": 8})
    else:
        conf.update({"num_layers": 2, "memory_layer": 1})
        conf["lram"]["tiered"].update({"shard_rows": 2048, "cache_slots": 8})
    return conf


def _inserted_smoke_config(conf: dict) -> dict:
    """A registered architecture with the memory FFN inserted: every field
    the file states takes the arch's smoke value (its cuts too), the
    memory layer is the second, the table has 2^16 rows, and the control
    is bfloat16, the step below the smoke size's float32."""
    from repro import configs

    small = configs.get_smoke_config(conf["arch"])
    for key in conf:
        field = harness.config_field(key, conf[key])
        if field and field[0] != "lram_layers":
            value = getattr(small, field[0])
            conf[key] = list(value) if isinstance(value, tuple) else value
    conf["act"] = {v: k for k, v in harness.ACTS.items()}[small.act]
    conf.update(smoke=True, memory_layer=1, control_dtype="bfloat16")
    conf["lram"].update(SMOKE_LRAM, heads=small.d_model // 16)
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
    cell["config"] = smoke_size(cell["config"])
    mix = cell["traffic"]
    if mix["kind"] in ("mlm", "clm"):
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
