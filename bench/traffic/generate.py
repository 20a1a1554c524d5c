"""The one generator behind every traffic file in this directory.

A traffic file (`<mix>.json`) is data: its `kind` picks one of the
generators below, and the rest of the file are its parameters.  Everything
is a pure function of the file, `--seed` and the window length, so the same
seed gives the same inputs.

Sizes and gaps are *stratified*: a mix of n requests takes the n quantiles
(i + 0.5)/n of each stated distribution, and the seed only permutes them.
Every seed thus asks for the same work, in another order, and the spread
between runs is the system's and not the draw's.

* `mlm`: training batches of `batch` sequences of `seq_len` tokens from a
  bounded Zipf stream (exponent `zipf_a`), `mask_prob` of positions chosen
  for the MLM loss and, of those, 80% replaced by the mask token (the
  vocabulary's last id), 10% by a random id, 10% kept (BERT's recipe).
  Copied from the program's `repro.data.synthetic`, without its planted
  facts.
* `clm`: causal-LM training batches: `batch` rows of `seq_len + 1` ids
  from the same stream; the first `seq_len` are the tokens and the last
  `seq_len` the labels (each position's next token), with no padding and
  nothing ignored.
* `requests`: serving requests.  `arrival: "poisson"` spaces `rate`
  requests per second with exponential gaps over the window;
  `arrival: "batch"` queues `count` requests at t = 0.  Prompt and output
  lengths are lognormal (`median`, `sigma`), rounded and clipped to
  [`min`, `max`]; prompt ids are uniform over the vocabulary.  Decoding is
  greedy.
"""

from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

IGNORE = -100


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed),
                                                         *stream]))


def _midquantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(spec: dict, n: int) -> np.ndarray:
    """The n mid-quantiles of lognormal(median, sigma), rounded, clipped."""
    inv = statistics.NormalDist().inv_cdf
    z = np.array([inv(p) for p in _midquantiles(n)])
    x = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    return -np.log1p(-_midquantiles(n)) / rate


# ------------------------------------------------------------------ training

def zipf_cdf(vocab: int, a: float) -> np.ndarray:
    ranks = np.arange(1, vocab)
    w = 1.0 / ranks ** a
    return np.cumsum(w) / w.sum()


def mlm_batch(mix: dict, vocab: int, seed: int, step: int,
              cdf: np.ndarray | None = None) -> dict:
    """{"tokens", "labels"} int32 (batch, seq_len) for one step."""
    rng = _rng(seed, 1, step)
    shape = (mix["batch"], mix["seq_len"])
    cdf = zipf_cdf(vocab, mix["zipf_a"]) if cdf is None else cdf
    tokens = np.searchsorted(cdf, rng.random(shape)).astype(np.int32)
    labels = np.full_like(tokens, IGNORE)
    mask = rng.random(shape) < mix["mask_prob"]
    labels[mask] = tokens[mask]
    action = rng.random(shape)
    tokens[mask & (action < 0.8)] = vocab - 1
    rand = mask & (action >= 0.8) & (action < 0.9)
    tokens[rand] = rng.integers(0, vocab - 1, size=int(rand.sum()))
    return {"tokens": tokens, "labels": labels}


def clm_batch(mix: dict, vocab: int, seed: int, step: int,
              cdf: np.ndarray | None = None) -> dict:
    """{"tokens", "labels"} int32 (batch, seq_len) for one step: labels
    are the tokens shifted by one."""
    rng = _rng(seed, 1, step)
    cdf = zipf_cdf(vocab, mix["zipf_a"]) if cdf is None else cdf
    ids = np.searchsorted(cdf, rng.random((mix["batch"], mix["seq_len"] + 1))
                          ).astype(np.int32)
    return {"tokens": ids[:, :-1], "labels": ids[:, 1:]}


TRAIN_BATCHES = {"mlm": mlm_batch, "clm": clm_batch}
TRAIN_KEYS = {"mlm": {"batch", "seq_len", "zipf_a", "mask_prob"},
              "clm": {"batch", "seq_len", "zipf_a"}}


def train_batch(mix: dict, vocab: int, seed: int, step: int,
                cdf: np.ndarray | None = None) -> dict:
    """Step `step`'s training batch of a training mix, by its `kind`."""
    return TRAIN_BATCHES[mix["kind"]](mix, vocab, seed, step, cdf)


# ------------------------------------------------------------------- serving

@dataclasses.dataclass
class Req:
    id: int
    due_s: float
    prompt: np.ndarray
    max_new_tokens: int


def request_count(mix: dict, seconds: float) -> int:
    if mix["arrival"] == "poisson":
        return max(1, int(round(mix["rate"] * seconds)))
    return int(mix["count"])


def requests(mix: dict, vocab: int, seed: int, seconds: float) -> list[Req]:
    """The mix's requests for a window of `seconds`, in order of due time."""
    n = request_count(mix, seconds)
    rng = _rng(seed, 2)
    prompts = rng.permutation(lognormal_lengths(mix["prompt"], n))
    outputs = rng.permutation(lognormal_lengths(mix["output"], n))
    if mix["arrival"] == "poisson":
        due = np.cumsum(rng.permutation(exponential_gaps(mix["rate"], n)))
    else:
        due = np.zeros(n)
    tok = _rng(seed, 3)
    return [Req(i, float(due[i]),
                tok.integers(0, vocab, size=int(prompts[i])).astype(np.int32),
                int(outputs[i])) for i in range(n)]


def length_range(mix: dict) -> tuple[int, int]:
    """(shortest, longest) prompt the mix can send."""
    return int(mix["prompt"]["min"]), int(mix["prompt"]["max"])


def check_mix(mix: dict) -> None:
    if mix["kind"] == "requests":
        for part in ("prompt", "output"):
            s = mix[part]
            if not (1 <= s["min"] <= s["max"]) or s["sigma"] < 0:
                raise ValueError(f"bad {part} lengths {s}")
        if mix["arrival"] not in ("poisson", "batch"):
            raise ValueError(f"unknown arrival {mix['arrival']!r}")
        if mix["arrival"] == "poisson" and not math.isfinite(mix["rate"]):
            raise ValueError("rate must be finite")
    elif mix["kind"] in TRAIN_KEYS:
        keys = TRAIN_KEYS[mix["kind"]]
        if set(mix) - {"kind", "about"} != keys:
            raise ValueError(f"a {mix['kind']} mix has the keys "
                             f"{sorted(keys)}, not {sorted(mix)}")
        for k in ("batch", "seq_len"):
            if not isinstance(mix[k], int) or mix[k] < 1:
                raise ValueError(f"{k} must be a whole number >= 1")
        if not (math.isfinite(mix["zipf_a"]) and mix["zipf_a"] > 0):
            raise ValueError("zipf_a must be finite and > 0")
        if "mask_prob" in mix and not 0 < mix["mask_prob"] <= 1:
            raise ValueError("mask_prob must be in (0, 1]")
    else:
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
