"""A later change adds a cell, a configuration, a traffic mix and a
per-layer metric with new files and new entries alone: here a dummy cell,
in a copy of the benchmark, runs end to end (at the smoke size, on the
CPU) without an edit to any file that was there."""

import json
import shutil
import subprocess
import sys

import pytest
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


# A causal-LM cell of a registered published architecture (Yi-9B: untied,
# RoPE, SwiGLU, GQA) with the memory FFN inserted by `configs.with_lram`,
# its depth cut through `reduced`, a `clm` mix and a reference module of
# its own; and the layout of a mixture-of-experts architecture (Mixtral)
# with a router and stacked expert weights.  New files and entries only.

DATA = harness.BENCH / "tests" / "data"
YI_FULL = harness.load_json(DATA / "yi-9b-lram.json")

CLM_SCRIPT = """
import json, sys
sys.path.insert(0, "bench")
import flops, harness, run
args = run.parse(["--workload", "dummy-clm", "--seed", "4294967311",
                  "--seconds", "1", "--trace", "0"])
done = run.run_cell(args, chips_required=False)
cell = harness.cell("dummy-clm")
inputs = {"steps": 4, "tokens_per_step": 32, "elapsed_s": 1.0, "seq_len": 16}
mfu = harness.reader("mfu.train").read({
    "cell": cell, "inputs": inputs, "peak": {"bf16_flops": 1e12},
    "flops": flops})
print(json.dumps({"result": done["result"], "checks": done["checks"],
                  "mfu": mfu, "config": cell["config"],
                  "per_token": flops.train_flops_per_token(cell["config"],
                                                           16)}))
"""

LAYOUT_SCRIPT = """
import json, sys
sys.path.insert(0, "bench")
import jax, numpy as np
import harness, weights
harness.program_path()
from repro.models import transformer
conf = harness.load_json(harness.BENCH / "configs" / "dummy-moe.json")
pcfg = harness.program_config(conf)
weights.check_layout(conf, *jax.eval_shape(
    lambda k: transformer.init(k, pcfg), jax.random.PRNGKey(0)))
params, _ = weights.make(conf, 7)
moe = params["segments"]["seg0"]["moe"]
leaves = {"router": moe["router"]["kernel"], **moe["experts"]}
print(json.dumps({k: [list(v.shape), float(np.std(np.asarray(v)))]
                  for k, v in leaves.items()}))
"""


def _bench_copy(tmp_path):
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (tmp_path / "src").symlink_to(harness.ROOT / "src")
    return {p.relative_to(tmp_path): p.read_bytes()
            for p in (tmp_path / "bench").rglob("*") if p.is_file()}


def _unchanged(tmp_path, before):
    after = {p.relative_to(tmp_path): p.read_bytes()
             for p in (tmp_path / "bench").rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    return all(after[k] == v for k, v in before.items())


def _run(tmp_path, argv, timeout=600):
    out = subprocess.run(argv, cwd=tmp_path, capture_output=True, text=True,
                         timeout=timeout,
                         env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                              "HOME": str(tmp_path)})
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout.strip().splitlines()


@pytest.fixture(scope="module")
def published_arch_copy(tmp_path_factory):
    """A copy of the benchmark with the dummy causal-LM cell and the MoE
    layout added as new files and entries; (root, the files before)."""
    root = tmp_path_factory.mktemp("published")
    before = _bench_copy(root)
    shutil.copy(DATA / "gqa_transformer.py", root / "bench/reference")
    shutil.copy(DATA / "moe_transformer.py", root / "bench/reference")
    conf = smoke.smoke_size(YI_FULL)
    # the cut bites at the smoke size too: 2 of the smoke arch's 3 layers
    conf.update(name="dummy-yi", num_layers=2)
    (root / "bench/configs/dummy-yi.json").write_text(json.dumps(conf))
    moe = dict(smoke.smoke_size(YI_FULL), name="dummy-moe",
               arch="mixtral-8x7b", reference="moe_transformer",
               family="moe", num_experts=4, top_k_experts=2,
               attention="swa", window=8)
    moe = smoke.smoke_size(moe)
    (root / "bench/configs/dummy-moe.json").write_text(json.dumps(moe))
    (root / "bench/traffic/dummy-clm.json").write_text(json.dumps(
        {"kind": "clm", "batch": 2, "seq_len": 16, "zipf_a": 1.1}))
    (root / "bench/workloads/dummy-clm.json").write_text(json.dumps(
        {"driver": "train", "limits": {"loss_gap": 0.01,
                                       "grad_norm_gap": 0.1,
                                       "update_norm_gap": 0.1}}))
    bench = harness.benchmark()
    bench["configs"].append({"name": "dummy-yi", "source": "test",
                             "file": "bench/configs/dummy-yi.json",
                             "reduced": ["num_layers"], "why": "test"})
    bench["workloads"].append({"name": "dummy-clm", "config": "dummy-yi",
                               "traffic": "dummy-clm", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("train_tokens_per_s", "mfu.train"):
            m["workloads"].append("dummy-clm")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root, before


def test_smoke_cut_of_a_published_arch():
    """The full-size statement is held to the registered arch and its
    stated cut; its smoke stand-in takes the arch's smoke sizes."""
    full = harness.program_config(YI_FULL)
    assert (full.num_layers, full.d_model, full.num_kv_heads,
            full.lram.heads, full.lram_layers) == (4, 4096, 4, 256, (2,))
    wrong = json.loads(json.dumps(YI_FULL))
    wrong["reduced"]["num_layers"]["published"] = 32
    with pytest.raises(harness.BenchError, match="registers num_layers"):
        harness.program_config(wrong)
    conf = smoke.smoke_size(YI_FULL)
    small = harness.program_config(conf)
    assert (small.num_layers, small.d_model, small.num_kv_heads,
            small.lram.heads, small.lram_layers) == (3, 64, 2, 4, (1,))
    assert conf["act"] == "swiglu" and conf["dtype"] == "float32"


def test_causal_lm_cell_of_a_published_arch_needs_only_new_files(
        published_arch_copy):
    root, before = published_arch_copy
    got = json.loads(_run(root, [sys.executable, "-c", CLM_SCRIPT])[-1])
    assert got["result"]["correct"], got["checks"]
    assert got["result"]["attempted"] > 0 and got["result"]["failed"] == 0
    assert set(got["result"]["metrics"]) == {"setup_s", "train_tokens_per_s"}
    # the model FLOPs are the GQA module's count, with the memory FFN
    c = got["config"]
    d, n, lr = c["d_model"], c["num_layers"], c["lram"]
    q, kv = c["num_heads"] * c["head_dim"], c["num_kv_heads"] * c["head_dim"]
    macs = (n * (2 * d * q + 2 * d * kv) + (n - 1) * 3 * d * c["d_ff"]
            + d * c["vocab_size"] + d * d + lr["heads"] * lr["m"] * d
            + lr["heads"] * (8 * 232 + lr["top_k"] * lr["m"]))
    attn = 2 * n * q * (16 + 1) / 2
    assert got["per_token"] == 6 * (macs + attn)
    assert got["mfu"] == pytest.approx(
        100 * got["per_token"] * 4 * 32 / 1.0 / 1e12)
    assert _unchanged(root, before)


def test_calibrate_runs_on_a_causal_lm_cell(published_arch_copy):
    root, before = published_arch_copy
    lines = _run(root, [sys.executable, "bench/calibrate.py", "train",
                        "--cell", "dummy-clm", "--seeds", "3",
                        "--control-seeds", "3"])
    got = json.loads(lines[-1])
    assert got["seed"] == 3
    for reading in ("program", "control", "half_batch"):
        assert got[reading]["loss_gap"] >= 0
    assert got["program"]["loss_gap"] < 0.01
    assert _unchanged(root, before)


def test_moe_layout_needs_only_new_files(published_arch_copy):
    """Router and stacked expert leaves: built by fan-in over shape[-2],
    and held to the program's own `init` shapes."""
    root, before = published_arch_copy
    got = json.loads(_run(root, [sys.executable, "-c", LAYOUT_SCRIPT])[-1])
    d, f, e = 48, 96, 4
    assert got["router"][0] == [1, d, e]
    assert got["wi_gate"][0] == got["wi_up"][0] == [1, e, d, f]
    assert got["wo"][0] == [1, e, f, d]
    for leaf, fan_in in (("router", d), ("wi_gate", d), ("wo", f)):
        assert abs(got[leaf][1] * fan_in ** 0.5 - 1) < 0.15, got[leaf]
    assert _unchanged(root, before)
