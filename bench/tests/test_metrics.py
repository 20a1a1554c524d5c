"""The benchmark's own arithmetic: model FLOPs and lookup bytes from
shapes, the per-layer readers, the trace's interval algebra, and the
numbers `correct` compares."""

import math

import jax.numpy as jnp
import numpy as np
import pytest
import smoke  # noqa: F401

import flops
import harness

BERT = harness.load_json(harness.BENCH / "configs" / "lram-bert-medium.json")
TIERED = harness.load_json(harness.BENCH / "configs" / "lram-tiered.json")
PEAK = harness.load_json(harness.BENCH / "peaks.json")["devices"][
    "TPU v5 lite"]


def test_per_token_macs_match_the_parameter_count():
    """Outside the lookup, a token multiplies every weight matrix once:
    the multiply-adds equal the non-embedding matrix parameters."""
    for conf in (BERT, TIERED):
        d, f, v = conf["d_model"], conf["d_ff"], conf["vocab_size"]
        mlp = (3 if conf["act"] == "swiglu" else 2) * d * f
        lr = conf["lram"]
        matrices = (conf["num_layers"] * 4 * d * d
                    + (conf["num_layers"] - 1) * mlp
                    + d * d + lr["heads"] * lr["m"] * d + d * v)
        lookup = lr["heads"] * (8 * 232 + lr["top_k"] * lr["m"])
        assert flops.per_token_macs(conf) == matrices + lookup


def test_train_flops_are_three_forwards():
    per = flops.train_flops_per_token(BERT, 128)
    fwd = flops.per_token_macs(BERT) + 2 * 6 * 8 * 64 * 128
    assert per == 6 * fwd
    # about 67 MFLOP per token forward: 2 x the ~34M weights a token meets
    assert 0.05e9 < per / 3 < 0.08e9


def test_forward_flops_of_a_request():
    one = flops.forward_flops(TIERED, 0, 1)
    assert one == 2 * (flops.per_token_macs(TIERED) + 2 * 6 * 512)
    total = flops.request_flops(TIERED, 10, 5)
    assert total == pytest.approx(sum(flops.forward_flops(TIERED, p, 1)
                                      for p in range(14)))


def test_train_cell_counts_are_unchanged():
    """The train cell's model FLOPs and lookup bytes, as the reference
    module counts them now and the harness counted them before."""
    assert flops.train_flops_per_token(BERT, 128) == 206_155_776
    assert flops.lookup_bytes(BERT, 2048, backward=True) == 1_644_167_168
    assert flops.per_token_macs(TIERED) == 30_951_424


def test_lookup_bytes():
    fwd = flops.lookup_bytes(BERT, 2048, backward=False)
    pairs = 2048 * 32 * 32
    assert fwd == pairs * (64 * 4 + 8)
    assert flops.lookup_bytes(BERT, 2048, backward=True) == \
        fwd + pairs * (2 * 64 * 4 + 8)


def _ctx(inputs, trace=None, conf=TIERED):
    return {"cell": {"config": conf}, "inputs": inputs, "trace": trace,
            "peak": PEAK, "flops": flops}


def test_readers():
    trace = {"busy_s": 2.5, "window_s": 10.0}
    assert harness.reader("device_idle.chat").read(_ctx({}, trace)) == 75.0
    assert harness.reader("device_idle.train").read(_ctx({})) is None
    inputs = {"elapsed_s": 2.0, "prefill_s": [0.1, 0.3], "step_s": [0.02,
                                                                    0.04],
              "store_stats": {"hits": 1, "misses": 1, "uncached": 2},
              "requests": [(10, 5)]}
    ctx = _ctx(inputs)
    assert harness.reader("prefill_ms.chat").read(ctx) == pytest.approx(200)
    assert harness.reader("decode_tick_ms.chat").read(ctx) == \
        pytest.approx(30)
    assert harness.reader("tiered_hit_rate.offline").read(ctx) == 25.0
    want = 100 * flops.request_flops(TIERED, 10, 5) / 2.0 / 197e12
    assert harness.reader("mfu.offline").read(ctx) == pytest.approx(want)
    train = {"steps": 10, "elapsed_s": 1.0, "tokens_per_step": 2048,
             "seq_len": 128}
    got = harness.reader("mfu.train").read(_ctx(train, conf=BERT))
    assert got == pytest.approx(100 * flops.train_flops_per_token(BERT, 128)
                                * 20480 / 197e12)
    assert harness.reader("prefill_ms.offline").read(_ctx({})) is None


def test_interval_algebra():
    import trace_reduce as tr

    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)]
    assert tr.union_length(iv) == 3.0
    assert tr.gaps(iv, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]
    assert tr.union_length([]) == 0.0


def test_leaf_gaps_take_the_worst_leaf():
    from drivers_train import worst_leaf_gap

    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    got = {"a": 1.1, "b": 2.0, "c": 1.0}
    gap, leaf = worst_leaf_gap(got, ref)
    # c's norm is measured against the median leaf's, 1.0
    assert leaf == "c" and gap == pytest.approx(1.0)
    # without c the median leaf is 1.5, above a's own norm
    gap, leaf = worst_leaf_gap(got, ref, keep=lambda k: k != "c")
    assert leaf == "a" and gap == pytest.approx(0.1 / 1.5)


def test_logit_gaps():
    from drivers_serve import logit_gaps, pack

    logits = jnp.asarray([[[0.0, 2.0, 1.0], [3.0, 0.0, 2.5]]])
    targets = jnp.asarray([[1, 2]])
    assert np.allclose(logit_gaps(logits, targets), [0.5])
    assert np.allclose(logit_gaps(logits, jnp.asarray([[1, -1]])), [0.0])

    class R:
        prompt = np.array([5, 6, 7], np.int32)
        tokens = [8, 9]

    toks, tgt = pack([R()], 8)
    assert toks.tolist() == [[5, 6, 7, 8, 0, 0, 0, 0]]
    assert tgt.tolist() == [[-1, -1, 8, 9, -1, -1, -1, -1]]


def test_quantile():
    assert harness.quantile_p(list(range(101)), 0.95) == 95.0
    assert math.isclose(harness.quantile_p([1.0, 2.0], 0.5), 1.5)
