"""A training cell's compiled step for a described TPU v5e (no chip), as HLO
text without its debug metadata: every `metadata={...}` and the stack-frame
tables.  What is left is the program the chip would run, so two checkouts
whose texts are equal run the same program, whatever their named scopes.

    JAX_PLATFORMS=cpu python3 bench/step_hlo.py [--cell C] --out FILE
    JAX_PLATFORMS=cpu python3 bench/step_hlo.py [--cell C] --compare DIR

`--compare` compiles the step of this checkout and of the checkout at DIR
(each in a process of its own, with that checkout's program and train
driver), prints both texts' sha256 and exits non-zero where they differ.
"""

from __future__ import annotations

import argparse
import hashlib
import os
import re
import subprocess
import sys
import tempfile
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
DEBUG_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def strip(hlo: str) -> str:
    out, skip = [], False
    for line in hlo.splitlines():
        if line in DEBUG_TABLES:
            skip = True
        elif skip and not line.strip():
            skip = False
        elif not skip:
            out.append(re.sub(r", metadata=\{[^}]*\}", "", line))
    return "\n".join(out) + "\n"


def compiled_step(root: Path, cell_name: str) -> str:
    """The compiled HLO text of the cell's step, built by the train driver
    of the checkout at `root` with that checkout's program."""
    sys.path.insert(0, str(root / "bench"))
    import harness

    harness.program_path()
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro import optim
    from repro.models import transformer

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    cell = harness.cell(cell_name)
    conf, mix = cell["config"], cell["traffic"]
    step = harness.driver(cell["workload"]).build(conf)
    pcfg = harness.program_config(conf)
    params, state = jax.eval_shape(lambda k: transformer.init(k, pcfg),
                                   jax.random.PRNGKey(0))
    opt = jax.eval_shape(optim.adam_init, params)

    def spec(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one), tree)

    batch = {k: jax.ShapeDtypeStruct((mix["batch"], mix["seq_len"]),
                                     jnp.int32, sharding=one)
             for k in ("tokens", "labels")}
    return step.lower(spec(params), spec(opt), spec(state),
                      jax.ShapeDtypeStruct((), jnp.float32, sharding=one),
                      batch).compile().as_text()


def _compile_in(root: Path, cell: str, out: Path) -> str:
    subprocess.run([sys.executable, __file__, "--root", str(root), "--cell",
                    cell, "--out", str(out)], check=True)
    return out.read_text()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", default="train-bert-medium-mlm128")
    ap.add_argument("--root", default=str(Path(__file__).resolve().parents[1]))
    ap.add_argument("--out")
    ap.add_argument("--compare")
    args = ap.parse_args(argv)
    if (args.out is None) == (args.compare is None):
        ap.error("give one of --out and --compare")
    root = Path(args.root).resolve()
    if args.compare is None:
        Path(args.out).write_text(strip(compiled_step(root, args.cell)))
        return 0
    with tempfile.TemporaryDirectory() as tmp:
        texts = [_compile_in(Path(r).resolve(), args.cell, Path(tmp) / f"{i}")
                 for i, r in enumerate((root, args.compare))]
    for r, t in zip((root, args.compare), texts):
        print(hashlib.sha256(t.encode()).hexdigest(), len(t), r)
    same = texts[0] == texts[1]
    print("identical" if same else "DIFFERENT")
    return 0 if same else 1


if __name__ == "__main__":
    sys.exit(main())
