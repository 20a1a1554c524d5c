"""`device_idle.train`: 1 - device busy time / traced window, in %."""
from _common import idle_percent as read  # noqa: F401
