"""What every cell's run shares: the data files it is driven by, the device
check, the compile cache, the clock, the trace, and the result line.

Nothing here knows a cell, a model or a traffic mix by name: a cell is
`BENCHMARK.json`'s entry plus `bench/workloads/<cell>.json`, which names
its driver (`bench/drivers/<driver>.py`); the configuration is
`bench/configs/<config>.json` with its reference module
`bench/reference/<reference>.py` (the model's mathematics, parameter
layout and model FLOPs), the traffic `bench/traffic/<mix>.json`, and each
per-layer metric a reader `bench/metrics/<metric>.py`.  A configuration
of a registered architecture states its cuts under `reduced`, and gets the
memory FFN inserted where the architecture has none (`program_config`).
"""

from __future__ import annotations

import contextlib
import functools
import glob
import importlib.util
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, a missing file, a
    configuration the program does not run as stated)."""


# ---------------------------------------------------------------- data files

def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    path = ROOT / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no {path.name} at the root of the checkout")
    return load_json(path)


def cell(name: str, bench: dict | None = None) -> dict:
    """The cell `name`, resolved into its files: {'name', 'config' (dict),
    'traffic' (dict), 'workload' (dict), 'entry' (BENCHMARK.json's)}."""
    bench = bench or benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    traffic = load_json(BENCH / "traffic" / f"{entry['traffic']}.json")
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    from traffic.generate import check_mix

    check_mix(traffic)
    return {
        "name": name,
        "entry": entry,
        "config": load_json(ROOT / conf["file"]),
        "traffic": traffic,
        "workload": load_json(BENCH / "workloads" / f"{name}.json"),
    }


def metrics_of(name: str, bench: dict) -> tuple[list[dict], list[dict]]:
    """(end_to_end, per_layer) metric entries that cell `name` reports: a
    metric with a `workloads` key lists its cells, one without it is in
    every cell."""
    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return ([m for m in bench["end_to_end"] if mine(m)],
            [m for m in bench["per_layer"] if mine(m)])


def load_module(path: Path, name: str | None = None):
    """Import a file by path (driver, reader, reference); file names may
    hold dots and dashes, so they are not imported by module name."""
    spec = importlib.util.spec_from_file_location(
        name or f"bench_{path.stem.replace('.', '_').replace('-', '_')}",
        path)
    if spec is None:
        raise BenchError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def reference(name: str):
    """The plain reference module `bench/reference/<name>.py`, loaded once
    a process: the model's mathematics, its parameter layout and its model
    FLOPs (see `bench/reference/lram_transformer.py`)."""
    return load_module(BENCH / "reference" / f"{name}.py",
                       f"bench_reference_{name.replace('-', '_')}")


def driver(workload: dict):
    return load_module(BENCH / "drivers" / f"{workload['driver']}.py")


def reader(metric: str):
    if str(BENCH / "metrics") not in sys.path:
        sys.path.insert(0, str(BENCH / "metrics"))
    return load_module(BENCH / "metrics" / f"{metric}.py")


# ------------------------------------------------------------------- device

def require_chips(chips: int) -> dict:
    """The device description of the result line; raises without an
    accelerator or with fewer chips than the cell asks for."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise BenchError("JAX found no accelerator (platform cpu)")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return describe(devices[:chips])


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def use_compile_cache() -> str | None:
    """JAX's persistent compilation cache: `JAX_COMPILATION_CACHE_DIR` where
    it is set, else `.jax_cache` at the root of the checkout (a fixed path,
    so that a later run in the same checkout finds it).  Every program is
    cached, however quickly it compiled, so that a second run compiles
    nothing.  The CPU backend (the tests) gets none."""
    import jax

    if jax.default_backend() == "cpu":
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def program_path() -> None:
    """Put the program's sources (`src/`) on the import path."""
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise BenchError(f"no program sources at {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


class CompileCounter:
    """Counts backend compiles (a program read from the persistent cache
    is not one), to show that none happens inside the window."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring

        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, _secs, **_kw):
        if name == self.EVENT:
            self.count += 1


# -------------------------------------------------------------------- trace

def annotate(name: str):
    """A host span in the profiler's own trace (`jax.profiler`)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def wrap_annotated(obj, attr: str, name: str) -> None:
    """Wrap the bound callable `obj.attr` on the instance in a host span;
    the program is not edited, and only a traced run wraps."""
    fn = getattr(obj, attr)

    def wrapped(*a, **kw):
        with annotate(name):
            return fn(*a, **kw)

    setattr(obj, attr, wrapped)


@contextlib.contextmanager
def profiled(out_dir: Path | None, record: dict):
    """Trace the body with `jax.profiler` into `out_dir` (no-op for None);
    `record['trace_file']` is the `.xplane.pb` written and
    `record['window_s']` the traced wall time."""
    if out_dir is None:
        yield
        return
    import jax

    out_dir.mkdir(parents=True, exist_ok=True)
    before = set(glob.glob(str(out_dir / "**" / "*.xplane.pb"),
                           recursive=True))
    jax.profiler.start_trace(str(out_dir))
    t0 = time.perf_counter()
    try:
        yield
    finally:
        record["window_s"] = time.perf_counter() - t0
        jax.profiler.stop_trace()
        new = sorted(set(glob.glob(str(out_dir / "**" / "*.xplane.pb"),
                                   recursive=True)) - before)
        record["trace_file"] = new[-1] if new else None


def trace_dir() -> Path:
    """Where a traced run writes its profile: under TMPDIR when given,
    else inside the checkout (`bench_out/`, ignored by git)."""
    base = os.environ.get("TMPDIR")
    root = Path(base) if base else ROOT / "bench_out"
    return root / f"bench_trace_{os.getpid()}"


# ------------------------------------------------------------------- result

def quantile_p(values, p: float) -> float:
    """The p-quantile (0..1) by linear interpolation between order
    statistics (numpy's default), over every value given."""
    import numpy as np

    return float(np.quantile(np.asarray(values, np.float64), p))


def emit(result: dict, checks: list[dict]) -> None:
    """The run's last lines: each number compared beside its limit on
    standard error, then the one JSON result line on standard output, with
    the same comparisons under its last key."""
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    result = dict(result)
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def log(**fields) -> None:
    """An informational line (never the last line of standard output)."""
    print(json.dumps(fields), flush=True)


# ------------------------------------------------------------ configuration

# A configuration file states ModelConfig fields by their names, but for
# `memory_layer` (`lram_layers`) and `act` (named as in ACTS); a field of
# UNSTATED that the file leaves out is held to the value there: a dense
# model of full attention.
ACTS = {"gelu_tanh": "gelu", "swiglu": "swiglu"}
UNSTATED = {"family": "dense", "num_experts": 0, "attention": "full",
            "pkm_layers": (), "tie_embeddings": False}
NOT_FIELDS = ("name", "lram")   # stated otherwise, or held apart


def config_field(key: str, value):
    """(ModelConfig field, value in the program's terms) of a file's key,
    or None for a key that names no field."""
    import dataclasses

    program_path()
    from repro.models.config import ModelConfig

    if key == "memory_layer":
        return "lram_layers", (value,)
    if key == "act":
        return "act", ACTS[value]
    names = {f.name for f in dataclasses.fields(ModelConfig)}
    if key not in names or key in NOT_FIELDS:
        return None
    return key, tuple(value) if isinstance(value, list) else value


def program_config(conf: dict):
    """The program's ModelConfig for a configuration file.

    It is the registered arch (`arch`; its smoke configuration where the
    file says `smoke`) with exactly the keys that the file's `reduced`
    object names replaced by the file's values (each entry gives the
    `published` value and `why`), the memory FFN inserted by
    `configs.with_lram` at `memory_layer` where the arch has none, and the
    table's placement swapped to `lram.placement`.  Every ModelConfig field
    and memory key the file states is then held to the program's value,
    and a difference raises."""
    import dataclasses

    program_path()
    from repro import configs

    get = configs.get_smoke_config if conf.get("smoke") else configs.get_config
    cfg = get(conf["arch"])
    cuts = {}
    for key, entry in conf.get("reduced", {}).items():
        field = config_field(key, conf[key])
        if field is None:
            raise BenchError(f"{conf['name']}: `reduced` names {key!r}, "
                             f"which is no field of the program's config")
        published = config_field(key, entry["published"])[1]
        if not conf.get("smoke") and getattr(cfg, field[0]) != published:
            raise BenchError(f"{conf['name']}: {conf['arch']} registers "
                             f"{key} = {getattr(cfg, field[0])!r}, the "
                             f"file's `reduced` says {published!r}")
        cuts[field[0]] = field[1]
    cfg = dataclasses.replace(cfg, **cuts)
    lr = conf["lram"]
    if cfg.lram is None:
        cfg = configs.with_lram(cfg, lr["log2_locations"],
                                conf["memory_layer"])
    if lr["placement"] != cfg.lram.interp_impl:
        cfg = configs.with_placement(cfg, lr["placement"])
    want = dict(UNSTATED)
    for key, value in conf.items():
        field = config_field(key, value)
        if field:
            want[field[0]] = field[1]
    got = {k: getattr(cfg, k) for k in want}
    got_lram = {"log2_locations": cfg.lram.log2_locations, "m": cfg.lram.m,
                "heads": cfg.lram.heads, "top_k": cfg.lram.top_k,
                "query_norm": cfg.lram.query_norm,
                "torus_K": list(cfg.lram.torus_spec.K),
                "placement": cfg.lram.interp_impl,
                "table_quant": cfg.lram.table_quant}
    want_lram = {k: lr[k] for k in got_lram}
    if cfg.lram.tiered is not None or "tiered" in lr:
        spec = cfg.lram.tiered
        keep = ("shard_rows", "cache_slots", "quant")
        got_lram["tiered"] = (None if spec is None else {
            k: v for k, v in dataclasses.asdict(spec).items() if k in keep})
        want_lram["tiered"] = lr.get("tiered")
    if got != want or got_lram != want_lram:
        diff = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        diff.update({k: (got_lram[k], want_lram[k]) for k in want_lram
                     if got_lram[k] != want_lram[k]})
        raise BenchError(f"{conf['name']}: the program runs "
                         f"{conf['arch']} otherwise than the file states "
                         f"(program, file): {diff}")
    return cfg
