"""Model FLOPs and the lattice lookup's necessary bytes, from the shapes in a
configuration file.  Nothing here reads the program or a trace.

Model FLOPs count the multiply-adds that the model's mathematics needs
(2 FLOPs each), not what an implementation happens to run: padding,
recomputation and the optimizer's elementwise work do not count.  What
depends on the architecture (attention, MLPs or experts, the output head)
is counted by the configuration's reference module
(`bench/reference/<reference>.py`: `macs_per_token`,
`attn_macs_per_context`).  The memory FFN, which every configuration
shares, is counted here: its two projections (w -> w, h*m -> w), the
distance products to the 232 candidate lattice points of each head's query
(paper §2.6, 8 x 232 multiply-adds), and the weighted sum of top_k rows of
m values (paper Table 3).  A backward pass counts twice its forward.
"""

from __future__ import annotations

import harness

CANDIDATES = 232      # lattice points within sqrt(8) of the fundamental region
LATTICE_DIM = 8


def memory_ffn_macs(cfg) -> int:
    """Multiply-adds per token of the memory FFN: its projections and the
    lookup."""
    d, lr = cfg["d_model"], cfg["lram"]
    proj = d * d + lr["heads"] * lr["m"] * d
    lookup = lr["heads"] * (LATTICE_DIM * CANDIDATES + lr["top_k"] * lr["m"])
    return proj + lookup


def per_token_macs(cfg) -> int:
    """Multiply-adds per token outside attention's context products."""
    return (harness.reference(cfg["reference"]).macs_per_token(cfg)
            + memory_ffn_macs(cfg))


def _attn_macs_per_context(cfg) -> int:
    """Multiply-adds per (query, key) pair over all layers."""
    return harness.reference(cfg["reference"]).attn_macs_per_context(cfg)


def train_flops_per_token(cfg, seq_len: int) -> float:
    """Forward and backward FLOPs per token of bidirectional (MLM) or
    causal (CLM) training on sequences of seq_len."""
    keys = seq_len if cfg["objective"] == "mlm" else (seq_len + 1) / 2
    fwd = per_token_macs(cfg) + _attn_macs_per_context(cfg) * keys
    return 3 * 2 * fwd


def forward_flops(cfg, first: int, count: int) -> float:
    """Forward FLOPs of `count` causal tokens at positions first ..
    first + count - 1 (token at position p attends to p + 1 keys)."""
    if count <= 0:
        return 0.0
    keys = count * first + count * (count + 1) / 2
    return 2 * (count * per_token_macs(cfg)
                + _attn_macs_per_context(cfg) * keys)


def request_flops(cfg, prompt_len: int, generated: int) -> float:
    """A served request: its prompt, then every generated token but the
    last, which is returned and never fed back."""
    return forward_flops(cfg, 0, prompt_len + max(generated - 1, 0))


def lookup_bytes(cfg, tokens: int, *, backward: bool) -> int:
    """HBM bytes the lattice lookup needs for `tokens` queries: each of the
    heads * top_k rows read once (m values of 4 bytes), the 4-byte row ids
    and weights read once, and, backward, each touched row read and written
    once for its update, with the ids and weights read again."""
    lr = cfg["lram"]
    pairs = tokens * lr["heads"] * lr["top_k"]
    row = lr["m"] * 4
    fwd = pairs * (row + 8)
    if not backward:
        return fwd
    return fwd + pairs * (2 * row + 8)
