"""Keep the docs honest: smoke-run documented commands, check links.

    python tools/check_docs.py [--level help|smoke] [--no-commands]

Two checks (CI runs both in the `docs` job; see .github/workflows/ci.yml):

1. **Dead links** — every relative markdown link in README.md and
   docs/**/*.md must resolve to an existing file.

2. **Documented commands run** — every `python ...` line inside a
   ```bash fence of README.md is executed so documented entry points
   cannot rot:

     * `--level help` (default): each command runs with `--help` — proves
       the module imports and exposes the documented CLI.
     * `--level smoke`: launcher commands run for real, rewritten to the
       smallest footprint (`--steps 2`, tiny gen/prompt sizes); benchmark
       commands run `--help`-level (their full sweeps are tier-2);
       `benchmarks.run <tables>` is checked by importing the selected
       table modules.

   `pip ...` and `pytest` lines are skipped (the install/tier-1 CI jobs
   own those).
"""

from __future__ import annotations

import argparse
import os
import re
import shlex
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SMOKE_OVERRIDES = {
    "repro.launch.train": ["--smoke", "--steps", "2", "--log-every", "1"],
    "repro.launch.serve": ["--smoke", "--batch", "2", "--prompt-len", "4",
                           "--gen", "3"],
}
# benchmark sweeps are tier-2; at smoke level only prove they import/parse
HELP_ONLY_AT_SMOKE = ("benchmarks.table",)


def iter_markdown_files():
    yield os.path.join(REPO, "README.md")
    docs = os.path.join(REPO, "docs")
    for root, _, files in os.walk(docs):
        for f in sorted(files):
            if f.endswith(".md"):
                yield os.path.join(root, f)


def check_links() -> list[str]:
    """Relative markdown links must point at existing files."""
    errors = []
    link_re = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
    for path in iter_markdown_files():
        text = open(path, encoding="utf-8").read()
        # strip fenced code blocks: `](` inside code is not a link
        text = re.sub(r"```.*?```", "", text, flags=re.S)
        for target in link_re.findall(text):
            if re.match(r"^[a-z]+:", target) or target.startswith("#"):
                continue  # external or intra-page anchor
            rel = target.split("#")[0]
            resolved = os.path.normpath(
                os.path.join(os.path.dirname(path), rel)
            )
            if not os.path.exists(resolved):
                errors.append(
                    f"{os.path.relpath(path, REPO)}: dead link -> {target}"
                )
    return errors


def documented_commands() -> list[list[str]]:
    """`python ...` lines from README bash fences (continuations joined)."""
    text = open(os.path.join(REPO, "README.md"), encoding="utf-8").read()
    cmds = []
    for block in re.findall(r"```bash\n(.*?)```", text, flags=re.S):
        block = block.replace("\\\n", " ")
        for line in block.splitlines():
            line = line.split("#")[0].strip()
            if line.startswith("python "):
                cmds.append(shlex.split(line))
    return cmds


def plan(cmd: list[str], level: str) -> list[str] | None:
    """Rewrite a documented command for the requested check level;
    None = skip."""
    if cmd[:2] == ["python", "-m"]:
        module, args = cmd[2], cmd[3:]
    else:
        return None  # `python chip_smoke.py` needs a TPU: not run here
    if module in ("pytest", "pip"):
        return None
    if module == "benchmarks.run":
        # prove the documented table selections resolve to real modules
        from importlib import import_module
        sys.path.insert(0, REPO)
        run_mod = import_module("benchmarks.run")
        mods = [m for m in run_mod.MODULES
                if not args or m.split("_")[0] in
                {a.split("_")[0] for a in args}]
        assert mods, f"no benchmark modules match {args}"
        return [sys.executable, "-c",
                ";".join(f"import benchmarks.{m}" for m in mods)]
    if level == "help" or module.startswith(HELP_ONLY_AT_SMOKE):
        return [sys.executable, "-m", module, "--help"]
    extra = SMOKE_OVERRIDES.get(module, [])
    return [sys.executable, "-m", module, *args, *extra]  # argparse: last wins


def check_commands(level: str) -> list[str]:
    errors = []
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src")
               + os.pathsep + os.environ.get("PYTHONPATH", ""))
    for cmd in documented_commands():
        final = plan(cmd, level)
        if final is None:
            print(f"SKIP  {' '.join(cmd)}")
            continue
        print(f"RUN   {' '.join(cmd)}  ->  {' '.join(final)}", flush=True)
        try:
            proc = subprocess.run(final, cwd=REPO, env=env,
                                  capture_output=True, text=True,
                                  timeout=1200)
        except subprocess.TimeoutExpired:
            errors.append(f"documented command timed out: {' '.join(cmd)}")
            continue
        if proc.returncode != 0:
            errors.append(
                f"documented command failed: {' '.join(cmd)}\n"
                f"  as: {' '.join(final)}\n"
                f"  stderr tail: {proc.stderr.strip()[-2000:]}"
            )
    return errors


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--level", choices=["help", "smoke"], default="help")
    ap.add_argument("--no-commands", action="store_true",
                    help="dead-link check only")
    args = ap.parse_args(argv)
    errors = check_links()
    if not args.no_commands:
        errors += check_commands(args.level)
    for e in errors:
        print(f"ERROR: {e}", file=sys.stderr)
    print(f"check_docs: {'FAIL' if errors else 'OK'} "
          f"({len(errors)} error(s))")
    return 1 if errors else 0


if __name__ == "__main__":
    raise SystemExit(main())
