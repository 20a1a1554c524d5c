"""`gather_roofline.train`: the lattice lookup's necessary HBM bytes
(`flops.lookup_bytes`, forward and backward) over the chip's peak
bandwidth, as a share of the device time of the ops traced under
`lram/gather`: the bytes of `lookup_roofline.train`, with the ops found by
scope in the step's HLO (`bench/scopes.py`) and not by shape.  A fusion
that spans two scopes counts in its root's.  No match: no reading."""
from scopes import scope_seconds


def read(ctx):
    inputs, peak = ctx["inputs"], ctx["peak"]
    got = scope_seconds(ctx, "lram/gather")
    if got is None or not peak or not inputs.get("tokens_per_step"):
        return None
    seconds, steps = got
    need = ctx["flops"].lookup_bytes(ctx["cell"]["config"],
                                     inputs["tokens_per_step"],
                                     backward=True) * steps
    return 100.0 * need / peak["hbm_bytes_per_s"] / seconds
