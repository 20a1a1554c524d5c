"""`mfu.chat`: model FLOPs of the window's prompts and generated tokens
over the window, as a share of the chip's bf16 peak."""
from _common import serve_mfu_percent as read  # noqa: F401
