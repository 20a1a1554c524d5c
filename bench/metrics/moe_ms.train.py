"""`moe_ms.train`: device time a step of the ops traced under `moe` (the
expert layers: routing and the router's bias update, dispatch, the grouped
expert products, combine and the shared experts, forward and backward), in
ms, found by scope in the step's HLO (`bench/scopes.py`).  No match (a
model without expert layers): no reading."""
from scopes import scope_ms_per_step


def read(ctx):
    return scope_ms_per_step(ctx, "moe")
