"""`tiered_hit_rate.offline`: the tiered store's hits / (hits + misses +
uncached) over the window, in % (the program's own counters)."""
from _common import hit_percent as read  # noqa: F401
