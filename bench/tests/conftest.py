"""The drivers are files loaded by path; give the tests module names for
them."""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

harness.program_path()
for name in ("train", "serve"):
    sys.modules.setdefault(f"drivers_{name}", harness.load_module(
        BENCH / "drivers" / f"{name}.py", f"drivers_{name}"))
