"""`decode_tick_ms.chat`: the engine's total `step_s` over its decode
ticks in the window, in ms per tick."""
from _common import mean_ms


def read(ctx):
    return mean_ms(ctx["inputs"].get("step_s"))
