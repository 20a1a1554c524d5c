"""The control: the plain reference computed in the configuration's
`control_dtype` (bfloat16, the step below float32) and, where it states
one, with its products' operands in `control_products`, put in the
program's place, must come out not correct.  On the chip it is read at
each cell's own size (`bench/calibrate.py`, PERF.md); here at a size a
test run holds, on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import smoke

import harness
import weights
from drivers_serve import logit_gaps
from drivers_train import readings, reference_run

REF = harness.load_module(harness.BENCH / "reference" /
                          "lram_transformer.py")


def test_train_control_fails_the_limits():
    cell = smoke.smoke_cell("train-bert-medium-mlm128")
    conf, mix = cell["config"], cell["traffic"]
    limits = cell["workload"]["limits"]
    with jax.default_matmul_precision("highest"):
        ref = reference_run(conf, mix, 11)
    ctl = reference_run(conf, mix, 11, dtype=jnp.dtype(conf["control_dtype"]))
    got = readings(ctl, ref)
    assert any(got[k] > limits[k] for k in limits), got


@pytest.mark.parametrize("cell_name", ["serve-tiered-chat"])
def test_serve_control_fails_the_limit(cell_name):
    """At the served configuration's own widths and depth (a smaller batch
    of shorter sequences): where a server serves the float32 reference's
    best token at every position, the control's choice (bfloat16 values,
    float8 products) lies further below it than the limit allows."""
    cell = smoke.full_cell(cell_name)
    conf = cell["config"]
    limit = cell["workload"]["limits"]["served_logit_gap"]
    tokens = np.random.default_rng(3).integers(
        0, conf["vocab_size"], size=(4, 256)).astype(np.int32)
    fwd = jax.jit(lambda p, s, t: REF.forward(conf, p, s, t)[0])
    params, state = weights.make(conf, conf["weight_seed"])
    with jax.default_matmul_precision("highest"):
        logits = fwd(params, state, tokens)
    served = jnp.argmax(logits, -1).astype(jnp.int32)
    assert float(jnp.max(logit_gaps(logits, served))) == 0.0
    low = weights.make(conf, conf["weight_seed"],
                       dtype=jnp.dtype(conf["control_dtype"]))
    low_conf = dict(conf, products=conf["control_products"])
    pick = jnp.argmax(jax.jit(lambda p, s, t: REF.forward(
        low_conf, p, s, t)[0])(*low, tokens), -1).astype(jnp.int32)
    assert float(jnp.max(logit_gaps(logits, served, pick))) > limit
