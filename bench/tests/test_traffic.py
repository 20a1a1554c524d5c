"""The traffic generator: a pure function of the file, the seed and the
window, and the same work for every seed."""

import numpy as np
import pytest
import smoke  # noqa: F401  (puts bench/ on the import path)
from traffic import generate as gen

import harness

CHAT = harness.load_json(harness.BENCH / "traffic" / "chat-poisson.json")
OFFLINE = harness.load_json(harness.BENCH / "traffic" / "offline-batch.json")
MLM = harness.load_json(harness.BENCH / "traffic" / "mlm128-zipf.json")
BIG_SEED = 2**31 + 12345


def _fingerprint(reqs):
    return [(r.id, r.due_s, r.max_new_tokens, r.prompt.tobytes())
            for r in reqs]


def test_requests_repeat_for_a_seed():
    a = gen.requests(CHAT, 30000, BIG_SEED, 30.0)
    b = gen.requests(CHAT, 30000, BIG_SEED, 30.0)
    assert _fingerprint(a) == _fingerprint(b)
    assert _fingerprint(a) != _fingerprint(gen.requests(CHAT, 30000, 7, 30.0))


def test_every_seed_gets_the_same_work():
    a = gen.requests(CHAT, 30000, 1, 30.0)
    b = gen.requests(CHAT, 30000, BIG_SEED, 30.0)
    assert len(a) == len(b) == round(CHAT["rate"] * 30.0)
    for key in (lambda r: r.prompt.size, lambda r: r.max_new_tokens):
        assert sorted(map(key, a)) == sorted(map(key, b))
    assert np.isclose(max(r.due_s for r in a), max(r.due_s for r in b))


def test_lengths_follow_the_mix():
    reqs = gen.requests(CHAT, 30000, 3, 40.0)
    prompts = np.array([r.prompt.size for r in reqs])
    outs = np.array([r.max_new_tokens for r in reqs])
    assert prompts.min() >= CHAT["prompt"]["min"]
    assert prompts.max() <= CHAT["prompt"]["max"]
    assert outs.min() >= CHAT["output"]["min"]
    assert abs(np.median(prompts) - CHAT["prompt"]["median"]) <= 2
    assert abs(np.median(outs) - CHAT["output"]["median"]) <= 2
    gaps = np.diff([r.due_s for r in reqs])
    assert np.all(gaps >= 0)
    assert abs(np.mean(gaps) - 1 / CHAT["rate"]) < 0.1 / CHAT["rate"]
    assert all(0 <= r.prompt.min() and r.prompt.max() < 30000 for r in reqs)


def test_batch_mix_is_queued_at_zero():
    reqs = gen.requests(OFFLINE, 30000, 5, 30.0)
    assert len(reqs) == OFFLINE["count"]
    assert all(r.due_s == 0.0 for r in reqs)


def test_mlm_batches():
    a = gen.mlm_batch(MLM, 30000, BIG_SEED, 3)
    b = gen.mlm_batch(MLM, 30000, BIG_SEED, 3)
    c = gen.mlm_batch(MLM, 30000, BIG_SEED, 4)
    for k in ("tokens", "labels"):
        assert a[k].shape == (MLM["batch"], MLM["seq_len"])
        assert a[k].dtype == np.int32
        np.testing.assert_array_equal(a[k], b[k])
    assert not np.array_equal(a["tokens"], c["tokens"])
    masked = a["labels"] != gen.IGNORE
    assert 0.08 < masked.mean() < 0.22
    assert np.all(a["tokens"] < 30000) and np.all(a["tokens"] >= 0)
    # most masked positions carry the mask token, the vocabulary's last id
    assert (a["tokens"][masked] == 29999).mean() > 0.6


def test_check_mix_refuses_bad_files():
    gen.check_mix(CHAT)
    gen.check_mix(MLM)
    bad = dict(CHAT, arrival="bursty")
    try:
        gen.check_mix(bad)
    except ValueError:
        return
    raise AssertionError("an unknown arrival process was accepted")


CLM = {"kind": "clm", "batch": 8, "seq_len": 64, "zipf_a": 1.2}


def test_clm_batches():
    a = gen.train_batch(CLM, 30000, BIG_SEED, 3)
    b = gen.clm_batch(CLM, 30000, BIG_SEED, 3)
    for k in ("tokens", "labels"):
        assert a[k].shape == (CLM["batch"], CLM["seq_len"])
        assert a[k].dtype == np.int32
        np.testing.assert_array_equal(a[k], b[k])
    # each label is the next token: no padding, nothing ignored
    np.testing.assert_array_equal(a["labels"][:, :-1], a["tokens"][:, 1:])
    assert np.all(a["labels"] >= 0)
    for ids in (a["tokens"], a["labels"]):
        assert ids.min() >= 0 and ids.max() < 30000 - 1
    for seed, step in ((7, 3), (BIG_SEED, 4)):
        c = gen.train_batch(CLM, 30000, seed, step)
        assert not np.array_equal(a["tokens"], c["tokens"])
        assert c["tokens"].shape == a["tokens"].shape
        assert c["labels"].size == a["labels"].size


def test_mlm_batch_is_unchanged():
    """The train cell's batches, byte for byte as before `clm` came."""
    import hashlib

    b = gen.train_batch(MLM, 30000, BIG_SEED, 3)
    digest = hashlib.sha256(b["tokens"].tobytes()
                            + b["labels"].tobytes()).hexdigest()
    assert digest == ("ba5e6c349d1baf5b89b2878e6404b500"
                      "b037da552870e9fa535ce3d56b378a82")


@pytest.mark.parametrize("bad", [
    dict(CLM, seq_len=0), dict(CLM, batch=2.5), dict(CLM, zipf_a=-1.0),
    dict(CLM, zipf_a=float("nan")), dict(CLM, mask_prob=0.15),
    {k: v for k, v in CLM.items() if k != "zipf_a"},
    dict(MLM, mask_prob=1.5), dict(CLM, kind="prefix-lm")])
def test_check_mix_refuses_bad_training_mixes(bad):
    gen.check_mix(CLM)
    with pytest.raises((ValueError, KeyError)):
        gen.check_mix(bad)
