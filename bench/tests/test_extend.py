"""A later change adds a cell, a configuration, a traffic mix and a
per-layer metric with new files and new entries alone: here a dummy cell,
in a copy of the benchmark, runs end to end (at the smoke size, on the
CPU) without an edit to any file that was there."""

import json
import shutil
import subprocess
import sys

import smoke

import harness

SCRIPT = """
import json, sys
sys.path.insert(0, "bench")
import harness, run
args = run.parse(["--workload", "dummy-cell", "--seed", "5",
                  "--seconds", "1", "--trace", "0"])
done = run.run_cell(args, chips_required=False)
reader = harness.reader("dummy_steps").read({"inputs": {"steps": 4}})
print(json.dumps({"result": done["result"], "reader": reader}))
"""


def test_dummy_cell_needs_only_new_files(tmp_path):
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (tmp_path / "src").symlink_to(harness.ROOT / "src")
    before = {p.relative_to(tmp_path): p.read_bytes()
              for p in (tmp_path / "bench").rglob("*") if p.is_file()}

    conf = smoke.smoke_config("lram-bert-medium")
    conf["name"] = "dummy-model"
    (tmp_path / "bench/configs/dummy-model.json").write_text(
        json.dumps(conf))
    (tmp_path / "bench/traffic/dummy-mix.json").write_text(json.dumps(
        {"kind": "mlm", "batch": 2, "seq_len": 16, "mask_prob": 0.15,
         "zipf_a": 1.1}))
    (tmp_path / "bench/workloads/dummy-cell.json").write_text(json.dumps(
        {"driver": "train", "limits": {"loss_gap": 0.01,
                                       "grad_norm_gap": 0.1,
                                       "update_norm_gap": 0.1}}))
    (tmp_path / "bench/metrics/dummy_steps.py").write_text(
        "def read(ctx):\n    return ctx['inputs'].get('steps')\n")
    bench = harness.benchmark()
    bench["configs"].append({"name": "dummy-model", "source": "test",
                             "file": "bench/configs/dummy-model.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "dummy-cell", "config": "dummy-model",
                               "traffic": "dummy-mix", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("dummy-cell")
    bench["per_layer"].append({"name": "dummy_steps", "unit": "steps",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "train_tokens_per_s",
                               "workloads": ["dummy-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    out = subprocess.run([sys.executable, "-c", SCRIPT], cwd=tmp_path,
                         capture_output=True, text=True, timeout=600,
                         env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                              "HOME": str(tmp_path)})
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["result"]["correct"]
    assert set(got["result"]["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert got["reader"] == 4
    after = {p.relative_to(tmp_path): p.read_bytes()
             for p in (tmp_path / "bench").rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert all(after[k] == v for k, v in before.items())
