"""`optimizer_ms.train`: device time a step of the ops traced under
`optim` (the global-norm clip and the Adam update of every parameter), in
ms, found by scope in the step's HLO (`bench/scopes.py`).  A fusion that
spans two scopes counts in its root's.  No match: no reading."""
from scopes import scope_ms_per_step


def read(ctx):
    return scope_ms_per_step(ctx, "optim")
