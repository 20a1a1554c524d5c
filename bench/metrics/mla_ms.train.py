"""`mla_ms.train`: device time a step of the ops traced under `mla` (the
latent attention of every layer: its projections, the latent's norm and
expansion, RoPE, scores and weighted values, forward and backward), in ms,
found by scope in the step's HLO (`bench/scopes.py`).  No match (a model
without latent attention): no reading."""
from scopes import scope_ms_per_step


def read(ctx):
    return scope_ms_per_step(ctx, "mla")
