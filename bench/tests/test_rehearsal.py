"""Each cell's run end to end on the CPU at the smoke size: the harness,
the driver, the program's timed path and the reference check, all but the
look for a chip (the command itself refuses the CPU)."""

import subprocess
import sys

import pytest
import smoke

import harness
import run

CELLS = ("train-bert-medium-mlm128", "serve-tiered-chat",
         "serve-tiered-offline")


def run_smoke(name, seed=4294967311, seconds=1.0):
    args = run.parse(["--workload", name, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", "0"])
    return run.run_cell(args, cell=smoke.smoke_cell(name),
                        chips_required=False)


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_is_correct(name):
    done = run_smoke(name)
    res = done["result"]
    assert res["correct"], done["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    bench = harness.benchmark()
    e2e, _ = harness.metrics_of(name, bench)
    assert set(res["metrics"]) == {m["name"] for m in e2e}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["device"]["count"] == 1


def test_command_refuses_the_cpu():
    out = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=harness.ROOT,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_command_refuses_a_checkout_without_the_program(tmp_path):
    """Only BENCHMARK.json and the benchmark's own files: no result."""
    import shutil

    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=tmp_path,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no program sources" in out.stderr
