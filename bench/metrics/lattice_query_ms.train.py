"""`lattice_query_ms.train`: device time a step of the ops traced under
`lram/query` (torus map, decode, distances, undoing the isometry, top-k,
encoding), forward and backward, in ms, found by scope in the step's HLO
(`bench/scopes.py`).  A fusion that spans two scopes counts in its root's.
No match: no reading."""
from scopes import scope_ms_per_step


def read(ctx):
    return scope_ms_per_step(ctx, "lram/query")
