"""Architecture registry: one module per assigned arch + the paper's models.

Every module exposes `config()` (the exact published configuration) and
`smoke_config()` (a reduced same-family config for CPU smoke tests).
`get_config(name)` / `get_smoke_config(name)` dispatch by arch id; shapes
live in repro.configs.shapes.  Beyond-paper archs: `lram-tiered`
(host-offloaded value table), `lram-tiered-q8` (the same with int8
rows + per-row scales on both tiers), and `lram-sharded-tiered`
(row-range-sharded tiered memory: each model shard owns a host-offloaded
range with its own hot cache); `with_lram(cfg)` inserts the paper's
memory FFN into any registered arch.
"""

from __future__ import annotations

import dataclasses
import importlib

from repro.core.lram import LRAMConfig
from repro.core import lram as lram_mod
from repro.models.config import ModelConfig

ARCHS = (
    "yi-9b",
    "qwen2-1.5b",
    "starcoder2-3b",
    "h2o-danube-3-4b",
    "zamba2-2.7b",
    "phi3.5-moe-42b-a6.6b",
    "mixtral-8x7b",
    "mamba2-1.3b",
    "whisper-small",
    "qwen2-vl-72b",
)

PAPER_MODELS = (
    "lram-bert-baseline",
    "lram-bert-pkm",
    "lram-bert-small",
    "lram-bert-medium",
    "lram-bert-large",
)

# beyond-paper configs: registered for get_config()/launchers, but kept out
# of the per-arch smoke matrix (they have their own tier-1 coverage)
EXTRA_MODELS = ("lram-tiered", "lram-tiered-q8", "lram-sharded-tiered")

_MODULES = {
    "yi-9b": "yi_9b",
    "qwen2-1.5b": "qwen2_1_5b",
    "starcoder2-3b": "starcoder2_3b",
    "h2o-danube-3-4b": "h2o_danube3_4b",
    "zamba2-2.7b": "zamba2_2_7b",
    "phi3.5-moe-42b-a6.6b": "phi3_5_moe",
    "mixtral-8x7b": "mixtral_8x7b",
    "mamba2-1.3b": "mamba2_1_3b",
    "whisper-small": "whisper_small",
    "qwen2-vl-72b": "qwen2_vl_72b",
    "lram-bert-baseline": "lram_bert",
    "lram-bert-pkm": "lram_bert",
    "lram-bert-small": "lram_bert",
    "lram-bert-medium": "lram_bert",
    "lram-bert-large": "lram_bert",
    "lram-tiered": "lram_tiered",
    "lram-tiered-q8": "lram_tiered_q8",
    "lram-sharded-tiered": "lram_sharded_tiered",
}


# every registered module is reachable from exactly one of the three lists
assert set(_MODULES) == set(ARCHS) | set(PAPER_MODELS) | set(EXTRA_MODELS)


def _module(name: str):
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_MODULES)}")
    return importlib.import_module(f"repro.configs.{_MODULES[name]}")


def get_config(name: str, **overrides) -> ModelConfig:
    mod = _module(name)
    if name.startswith("lram-bert"):
        cfg = mod.config(variant=name.removeprefix("lram-bert-"))
    else:
        cfg = mod.config()
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def get_smoke_config(name: str, **overrides) -> ModelConfig:
    mod = _module(name)
    if name.startswith("lram-bert"):
        cfg = mod.smoke_config(variant=name.removeprefix("lram-bert-"))
    else:
        cfg = mod.smoke_config()
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def with_placement(cfg: ModelConfig, placement: str) -> ModelConfig:
    """`cfg` with its memory table's lookup placement
    (`LRAMConfig.interp_impl`) replaced — the drivers' `--placement`."""
    if cfg.lram is None:
        raise SystemExit(f"--placement needs a memory arch; {cfg.name} "
                         f"has no LRAM layer")
    return dataclasses.replace(
        cfg, lram=dataclasses.replace(cfg.lram, interp_impl=placement)
    )


def with_lram(cfg: ModelConfig, log2_locations: int = 20,
              layer: int | None = None) -> ModelConfig:
    """Insert the paper's memory-augmented FFN at one layer of any arch."""
    layer = cfg.num_layers // 2 if layer is None else layer
    return dataclasses.replace(
        cfg,
        name=f"{cfg.name}+lram{log2_locations}",
        lram_layers=(layer,),
        lram=lram_mod.memffn_config(
            cfg.d_model, log2_locations, query_norm="batch"
        ),
    )
