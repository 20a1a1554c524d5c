"""The Moonlight-16B-A3B cell (`moonlight-16b-a3b-lram`: latent attention, a
leading dense layer, dropless sigmoid-routed experts with shared experts,
the lattice memory in its 4th layer) at the smoke size on the CPU, and the
readers of its per-layer metrics on the smoke step's compiled HLO."""

import pytest
import smoke

import flops
import harness
import run
import scopes

CELL = "train-moonlight-lram-clm1k"
FULL = harness.load_json(harness.BENCH / "configs" /
                         "moonlight-16b-a3b-lram.json")


def test_statement_holds_the_catalog_and_its_cuts():
    """The full-size file is the registered arch with exactly the three
    stated cuts, every other published number as the catalog has it."""
    cfg = harness.program_config(FULL)
    assert (cfg.num_layers, cfg.experts_held, cfg.vocab_size) == (5, 8, 20480)
    assert cfg.lram_layers == (3,) and cfg.lram.heads == 128
    assert sorted(FULL["reduced"]) == ["experts_held", "num_layers",
                                       "vocab_size"]
    assert (FULL["num_hidden_layers"], FULL["n_routed_experts"],
            FULL["num_experts_per_tok"]) == (27, 64, 6)
    assert 560e6 < cfg.param_count() < 580e6, cfg.param_count()
    mix = harness.cell(CELL)["traffic"]
    assert (mix["kind"], mix["batch"], mix["seq_len"]) == ("clm", 1, 1024)


def test_model_flops_count_what_a_token_meets_here():
    c = FULL
    d, f, h = c["d_model"], c["moe_intermediate_size"], c["num_heads"]
    attn = (d * h * 192 + d * (512 + 64) + 512 * h * 256 + h * 128 * d)
    expert = d * 64 + (6 * 8 / 64 + 2) * 3 * d * f
    want = 5 * attn + 3 * d * c["d_ff"] + 3 * expert + d * 20480
    ref = harness.reference(c["reference"])
    assert ref.macs_per_token(c) == int(want)
    assert ref.attn_macs_per_context(c) == 5 * 16 * (192 + 128)
    assert flops.per_token_macs(c) == int(want) + flops.memory_ffn_macs(c)


def test_cell_runs_correct_at_smoke_size():
    cell = smoke.smoke_cell(CELL)
    assert cell["config"]["experts_held"] < cell["config"]["num_experts"]
    args = run.parse(["--workload", CELL, "--seed", "4294967311",
                      "--seconds", "1", "--trace", "0"])
    done = run.run_cell(args, cell=cell, chips_required=False)
    res = done["result"]
    assert res["correct"], done["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "train_tokens_per_s"}


@pytest.fixture(scope="module")
def smoke_step():
    """(conf, mix, the smoke step's compiled HLO text, the expert counters
    two steps returned)."""
    drv = harness.driver({"driver": "train"})
    cell = smoke.smoke_cell(CELL)
    conf, mix = cell["config"], cell["traffic"]
    step = drv.build(conf)
    prog, (params, opt, state, res), feed = drv.first_steps(step, conf, mix,
                                                            5)
    hlo = step.lower(params, opt, state, res, feed(3)).compile().as_text()
    returned = []
    for i in range(2):
        params, opt, state, res, m = step(params, opt, state, res,
                                          feed(3 + i))
        returned.append({k: int(m[f"moe.{k}"])
                         for k in ("routed_copies", "max_expert_load")})
    return conf, mix, hlo, returned


def test_step_returns_its_expert_counters(smoke_step):
    """Copies of the held experts, summed over the expert layers: no more
    than every copy of every token, and one expert's at most all of them."""
    conf, mix, _, returned = smoke_step
    layers = conf["num_layers"] - conf["first_k_dense_replace"] - 1
    most = mix["batch"] * mix["seq_len"] * conf["top_k_experts"] * layers
    for c in returned:
        assert 0 < c["max_expert_load"] <= c["routed_copies"] <= most


def test_new_readers_read_the_smoke_step(smoke_step):
    conf, _, hlo, _ = smoke_step
    known = scopes.op_scopes(hlo)
    for scope in ("mla/q", "mla/kv_latent", "mla/scores", "moe/route",
                  "moe/dispatch", "moe/experts", "moe/combine",
                  "moe/shared"):
        assert any(scopes.under(v["segments"], scope)
                   for v in known.values()), scope
    # every instruction ran 4 times, a millisecond each time
    trace = {"ops": {f"%{n} = op": {"s": 4e-3, "count": 4}
                     for n, v in known.items() if v["entry"]}}
    ctx = {"cell": {"config": conf}, "trace": trace, "hlo": hlo,
           "peak": {"bf16_flops": 1e12}, "inputs": {}, "flops": flops}
    mla = harness.reader("mla_ms.train").read(ctx)
    moe = harness.reader("moe_ms.train").read(ctx)
    assert mla > 0 and moe > 0
