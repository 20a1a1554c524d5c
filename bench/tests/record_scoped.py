"""Record the fixture of `test_scopes.py` on a chip: a traced window of a
few steps of the train cell at the smoke size (`smoke.smoke_cell`), through
the train driver as a `--trace 1` run drives it, and the step's compiled
HLO text as the profile holds it, trimmed to what `bench/scopes.py` reads.

    python3 bench/tests/record_scoped.py <out_dir>

writes `<out_dir>/scoped.xplane.pb` and `<out_dir>/scoped_hlo.txt`; copy
both into `bench/tests/data/`.  Needs the accelerator it records.
"""

from __future__ import annotations

import re
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import smoke  # noqa: E402

import harness  # noqa: E402
import scopes  # noqa: E402

CELL = "train-bert-medium-mlm128"
SEED = 4294967311
SECONDS = 0.02   # a few steps: the profile stays small


def drop_plane(xspace: bytes, name: str) -> bytes:
    """The serialized `XSpace` without its planes (field 1) named `name`
    (an `XPlane`'s field 2)."""
    out = []
    for field, wire, start, value, end in scopes.fields(xspace):
        if field == 1 and wire == 2:
            plane = xspace[value:end]
            if any(f == 2 and w == 2 and plane[v:e] == name.encode()
                   for f, w, _, v, e in scopes.fields(plane)):
                continue
        out.append(xspace[start:end])
    return b"".join(out)


def _calls(line: str) -> bool:
    m = scopes._INSTR.match(line)
    return bool(m and scopes._called(m.group(2)))


def trim(hlo: str) -> str:
    """The HLO text that `bench/scopes.py` reads: the module line and the
    computations that run as device operations, with the instructions that
    have an `op_name` or run a computation; without the fused computations,
    the stack-frame tables, source locations and backend configs."""
    keep = set(scopes.device_computations(hlo))
    out, cur = [], None
    for line in hlo.splitlines():
        if cur is None:
            m = scopes._COMP.match(line)
            if m:
                cur = m.group(2)
                if cur in keep:
                    out.append(line)
            elif line.startswith("HloModule"):
                out.append(line.split(",", 1)[0])
            continue
        if line.strip() == "}":
            if cur in keep:
                out += ["}", ""]
            cur = None
        elif cur in keep and ("op_name=" in line or _calls(line)):
            line = re.sub(r" (stack_frame_id|source_line)=\d+"
                          r'| source_file="[^"]*"', "", line)
            out.append(re.sub(r", backend_config=(?!.*metadata=).*$", "",
                              line))
    return "\n".join(out) + "\n"


def main(out_dir: str) -> int:
    harness.use_compile_cache()
    harness.require_chips(1)
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    cell = smoke.smoke_cell(CELL)
    with tempfile.TemporaryDirectory() as tmp:
        ctx = smoke.context(cell, seed=SEED, seconds=SECONDS, trace=True,
                            trace_dir=Path(tmp))
        got = harness.driver(cell["workload"]).run(ctx)
        trace_file = got["trace"]["trace_file"]
        hlo = scopes.profile_hlo(trace_file)
        # the programs' HLO protos (1.4 MB of 2.2 MB here) go: the fixture
        # keeps the window's program as text in scoped_hlo.txt
        (out / "scoped.xplane.pb").write_bytes(drop_plane(
            Path(trace_file).read_bytes(),
            scopes.METADATA_PLANE.decode()))
    (out / "scoped_hlo.txt").write_text(trim(hlo))
    harness.log(steps=got["attempted"], checks=got["checks"],
                device=harness.describe(ctx.devices))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
