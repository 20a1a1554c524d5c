"""`prefill_ms.chat`: mean of the engine's `prefill_s` over the window's
admissions, in ms."""
from _common import mean_ms


def read(ctx):
    return mean_ms(ctx["inputs"].get("prefill_s"))
