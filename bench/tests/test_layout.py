"""What the train cell reads stays as it was while the harness learns other
architectures: the program's config of each configuration file, the
weights' layout and seeded values, and (in `test_metrics.py`) the model
FLOPs and lookup bytes."""

import dataclasses
import hashlib

import numpy as np
import pytest
import smoke

import harness
import weights
from repro import configs

FILES = ("lram-bert-medium", "lram-tiered")
# sha256 of the (path, shape) lists of each file's (params, state) layout
LAYOUTS = {
    "lram-bert-medium": "85b93f1c12662ee06271491efbfc3a73"
                        "29e62dd6625b32ec1f79d0cfe5c2bc33",
    "lram-tiered": "963ea2b53bc78e5777485f1c19cee0ed"
                   "58ad054659bcdbb5d96e0545f88aa89e",
}
# sha256 of every leaf's path, dtype, shape and bytes, smoke size, one seed
SMOKE_WEIGHTS = {
    "lram-bert-medium": "64f3508f88e01c1078fa302024329b45"
                        "c076f40d1d75817fde42f327b282a7c9",
    "lram-tiered": "7656480e87d922cd4f9ae060b4361cebd"
                   "ec62575b52664786e2bfd245cde29b8",
}


def _conf(name):
    return harness.load_json(harness.BENCH / "configs" / f"{name}.json")


@pytest.mark.parametrize("name", FILES)
def test_program_config_is_the_registered_arch(name):
    """No cut, no insertion, no swap: field for field the registered
    configuration, at full size and at the smoke size."""
    conf = _conf(name)
    got = harness.program_config(conf)
    assert dataclasses.asdict(got) == dataclasses.asdict(
        configs.get_config(conf["arch"]))
    small = harness.program_config(smoke.smoke_config(name))
    assert dataclasses.asdict(small) == dataclasses.asdict(
        configs.get_smoke_config(conf["arch"]))


@pytest.mark.parametrize("name", FILES)
def test_layout_is_unchanged(name):
    lay = weights.layout(_conf(name))
    digest = hashlib.sha256(repr([list(weights._paths(t)) for t in lay])
                            .encode()).hexdigest()
    assert digest == LAYOUTS[name]


@pytest.mark.parametrize("name", FILES)
def test_smoke_weights_are_unchanged(name):
    params, state = weights.make(smoke.smoke_config(name), 4294967311)
    h = hashlib.sha256()
    for path, x in weights._paths({"p": params, "s": state}):
        a = np.asarray(x)
        for part in (path, str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    assert h.hexdigest() == SMOKE_WEIGHTS[name]


def test_unnamed_leaves_take_fan_in():
    """Any weight of rank 2 or more, by fan-in over shape[-2]; an unnamed
    vector has no initialiser."""
    conf = _conf("lram-bert-medium")
    key = weights.prng_key(3)
    x = np.asarray(weights._leaf_init(conf, "seg0/moe/experts/wo",
                                      (4, 256, 64), key))
    assert abs(np.std(x) * 16 - 1) < 0.05
    with pytest.raises(ValueError, match="no initializer"):
        weights._leaf_init(conf, "seg0/mamba/A_log", (8,), key)


def test_a_cut_must_name_a_field():
    conf = dict(_conf("lram-bert-medium"),
                reduced={"norm_eps": {"published": 1e-5, "why": "test"}})
    with pytest.raises(harness.BenchError, match="no field"):
        harness.program_config(conf)
