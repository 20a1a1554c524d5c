"""The parameter layout of a mixture-of-experts causal LM (Mixtral-style:
a router and stacked expert SwiGLU weights in every layer but the memory
layer), for a test that a new architecture's leaves are built by
`bench/weights.py` with no edit there.  Layout only: no mathematics."""

from __future__ import annotations

import harness

lt = harness.reference("lram_transformer")


def moe_layer_shapes(cfg) -> dict:
    d, f, e = cfg["d_model"], cfg["d_ff"], cfg["num_experts"]
    return dict(lt.attention_block_shapes(cfg), moe={
        "router": {"kernel": (d, e)},
        "experts": {"wi_gate": (e, d, f), "wi_up": (e, d, f),
                    "wo": (e, f, d)}})


def layout(cfg):
    return lt.layout(cfg, layer=moe_layer_shapes)
