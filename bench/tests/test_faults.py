"""A run whose timed path is broken underneath must come out not correct:
for each fault a cell can have, the harness runs at the smoke size on the
CPU, with the program's step or engine patched, and `correct` is false.
(One chip: no exchange between chips to leave out.)"""

import jax
import jax.numpy as jnp
import pytest
from test_rehearsal import run_smoke

from repro.launch import train as train_mod
from repro.serving import engine as engine_mod


def _broken_train(monkeypatch, fault):
    real_build = train_mod.build_train_step

    def build(*a, **kw):
        real = real_build(*a, **kw)

        def step(params, opt, state, residual, batch):
            if fault == "unchanged":
                copy = lambda t: jax.tree.map(jnp.copy, t)  # noqa: E731
                out = real(copy(params), copy(opt), state, residual, batch)
                return (params, opt, state, residual, out[4])
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return real(params, opt, state, residual, half)

        return step

    monkeypatch.setattr(train_mod, "build_train_step", build)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_train_fault_is_not_correct(monkeypatch, fault):
    _broken_train(monkeypatch, fault)
    done = run_smoke("train-bert-medium-mlm128")
    assert not done["result"]["correct"], done["checks"]


@pytest.mark.parametrize("name", ["serve-tiered-chat",
                                  "serve-tiered-offline"])
def test_altered_token_is_not_correct(monkeypatch, name):
    """The decode step serves its second-best token: a token altered where
    it is produced."""
    real_swap = engine_mod.ServeEngine.swap_model

    def swap(self, params, cfg=None):
        real_swap(self, params, cfg)
        decode = self._decode

        def altered(tok, pos, cache):
            logits, cache = decode(tok, pos, cache)
            best = jnp.argmax(logits, -1, keepdims=True)
            return jnp.where(jnp.arange(logits.shape[-1]) == best, -jnp.inf,
                             logits), cache

        self._decode = altered

    monkeypatch.setattr(engine_mod.ServeEngine, "swap_model", swap)
    done = run_smoke(name)
    assert not done["result"]["correct"], done["checks"]
