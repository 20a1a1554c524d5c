"""Latent attention, the dropless expert layer and its shares, leading dense
layers, and the Moonlight (DeepSeek-V3) train step against the plain
reference in `bench/reference/mla_moe_transformer.py`, at smoke size."""

import dataclasses
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import run_in_subprocess

from repro import configs, optim
from repro.launch import train as train_mod
from repro.models import attention, moe, transformer

MOON = "moonlight-16b-a3b"
KEY = jax.random.PRNGKey(0)
BENCH = Path(__file__).resolve().parents[1] / "bench"


def _x(cfg, b=2, s=16, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, s, cfg.d_model))


# ------------------------------------------------------------------- MLA

def _rope(x, theta):
    """Rotate-half RoPE written out: x (B, S, H, D)."""
    s, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(0, d, 2) / d)
    ang = np.arange(s)[:, None] * freqs[None]
    cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mla_equations(p, x, cfg):
    """DeepSeek-V2 §2.1 without query compression, in numpy float64."""
    x = np.asarray(x, np.float64)
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), p)
    b, s, _ = x.shape
    h, r = cfg.num_heads, cfg.kv_lora_rank
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q = (x @ p["wq"]["kernel"]).reshape(b, s, h, nope + rope)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], cfg.rope_theta)
    a = x @ p["wkv_a"]["kernel"]
    c = a[..., :r]
    c = c / np.sqrt(np.mean(c * c, -1, keepdims=True) + cfg.rms_norm_eps)
    c = c * p["kv_norm"]["scale"]
    k_rope = _rope(a[..., None, r:], cfg.rope_theta)[:, :, 0]
    kv = (c @ p["wkv_b"]["kernel"]).reshape(b, s, h, nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = (np.einsum("bshd,bthd->bhst", q_nope, k_nope)
              + np.einsum("bshd,btd->bhst", q_rope, k_rope))
    scores = scores / np.sqrt(nope + rope)
    scores = np.where(np.tril(np.ones((s, s), bool)), scores, -np.inf)
    w = np.exp(scores - scores.max(-1, keepdims=True))
    w = w / w.sum(-1, keepdims=True)
    out = np.einsum("bhst,bthd->bshd", w, v).reshape(b, s, h * dv)
    return out @ p["wo"]["kernel"]


@pytest.mark.parametrize("impl", ["dense", "chunked"])
def test_mla_matches_its_equations(impl):
    cfg = dataclasses.replace(configs.get_smoke_config(MOON), attn_impl=impl,
                              attn_chunk=8)
    p = attention.attn_init(KEY, cfg)
    p["kv_norm"]["scale"] = 1 + 0.1 * jax.random.normal(
        jax.random.PRNGKey(3), p["kv_norm"]["scale"].shape)
    x = _x(cfg)
    pos = jnp.broadcast_to(jnp.arange(16), (2, 16))
    with jax.default_matmul_precision("highest"):
        y, (k, v) = attention.attn_apply(p, x, cfg, positions=pos)
    assert k.shape[-1] == cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    assert v.shape[-1] == cfg.v_head_dim
    np.testing.assert_allclose(np.asarray(y), _mla_equations(p, x, cfg),
                               rtol=1e-4, atol=1e-4)


def test_mla_gradient_matches_between_paths():
    cfg = configs.get_smoke_config(MOON)
    p = attention.attn_init(KEY, cfg)
    x = _x(cfg)
    pos = jnp.broadcast_to(jnp.arange(16), (2, 16))

    def grads(impl):
        c = dataclasses.replace(cfg, attn_impl=impl, attn_chunk=8)
        return jax.grad(lambda p, x: jnp.sum(jnp.sin(attention.attn_apply(
            p, x, c, positions=pos)[0])), argnums=(0, 1))(p, x)

    with jax.default_matmul_precision("highest"):
        for a, b in zip(jax.tree.leaves(grads("dense")),
                        jax.tree.leaves(grads("chunked"))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


# -------------------------------------------------------------- experts

def _skewed(cfg, params, hot=2):
    """Router weights that send most tokens of `_x_skew` to expert `hot`."""
    kern = params["router"]["kernel"]
    bump = jnp.zeros_like(kern).at[:, hot].set(4.0 / cfg.d_model)
    return dict(params, router={"kernel": kern + bump})


def _x_skew(cfg, s=24):
    """Inputs of mean 1 in every feature, which `_skewed` routers favour."""
    return _x(cfg, s=s) + 1.0


@pytest.mark.parametrize("arch", [MOON, "mixtral-8x7b"])
def test_dropless_layer_matches_dense_reference_under_skew(arch):
    cfg = configs.get_smoke_config(arch)
    params, state = moe.moe_init(KEY, cfg)
    params = _skewed(cfg, params)
    x = _x_skew(cfg)
    with jax.default_matmul_precision("highest"):
        y, aux, _, cnt = jax.jit(lambda p, s, x: moe.moe_apply(
            p, s, x, cfg))(params, state, x)
        want = moe.moe_apply_dense_reference(params, state, x, cfg)
        _, ids, _ = moe.route(params, state, x.reshape(-1, cfg.d_model), cfg)
    # one expert takes most tokens, and none of its copies is dropped
    load = np.bincount(np.asarray(ids).ravel(), minlength=cfg.num_experts)
    assert load[2] > 0.8 * x.shape[0] * x.shape[1]
    held = load[:cfg.experts_held]
    assert int(cnt["routed_copies"]) == held.sum()
    assert int(cnt["max_expert_load"]) == held.max()
    assert bool(jnp.isfinite(aux))
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-4, atol=1e-5 * scale)

    def loss(f):
        return lambda p, x: jnp.sum(jnp.tanh(f(p, x)))

    with jax.default_matmul_precision("highest"):
        g = jax.grad(loss(lambda p, x: moe.moe_apply(p, state, x, cfg)[0]),
                     argnums=(0, 1))(params, x)
        gw = jax.grad(loss(lambda p, x: moe.moe_apply_dense_reference(
            p, state, x, cfg)), argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gw)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3,
                                   atol=1e-5 * float(jnp.abs(b).max()))


def test_expert_shares_add_up_to_the_uncut_layer():
    """Eight shares of one expert each: their outputs, with the shared
    experts every share computes counted once, add up to the layer that
    holds all eight."""
    full_cfg = dataclasses.replace(configs.get_smoke_config(MOON),
                                   experts_held=8)
    params, state = moe.moe_init(KEY, full_cfg)
    params = _skewed(full_cfg, params, hot=5)
    x = _x_skew(full_cfg)
    share_cfg = dataclasses.replace(full_cfg, experts_held=1)
    with jax.default_matmul_precision("highest"):
        whole, _, _, whole_cnt = moe.moe_apply(params, state, x, full_cfg)
        shared = moe.mlp.mlp_apply(params["shared"], x, full_cfg)
        total, copies = -7 * shared, 0
        for first in range(8):
            mine = dict(params, experts=jax.tree.map(
                lambda a, f=first: a[f:f + 1], params["experts"]))
            y, _, _, cnt = moe.moe_apply(mine, state, x, share_cfg,
                                         first=first)
            total, copies = total + y, copies + int(cnt["routed_copies"])
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-4,
                               atol=1e-5 * float(jnp.abs(whole).max()))
    assert copies == int(whole_cnt["routed_copies"]) == x.shape[0] * \
        x.shape[1] * full_cfg.top_k_experts


@pytest.mark.parametrize("arch,mesh_shape", [
    ("mixtral-8x7b", (2, 4)),       # one expert a device
    ("mixtral-8x7b", (1, 8)),       # 4 experts on 8: hidden width split
    (MOON, (2, 4)),                 # 4 held of 8, one a device, shared
])
def test_expert_layer_on_a_mesh_matches_one_device(arch, mesh_shape):
    """The train step's loss and gradients over a data x model mesh, where
    the grouped products run in a shard_map over `model`, equal one
    device's."""
    run_in_subprocess(textwrap.dedent(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro import configs, data
        from repro.distributed import context, sharding
        from repro.launch.mesh import make_mesh
        from repro.models import transformer

        cfg = configs.get_smoke_config({arch!r})
        dcfg = data.DataConfig(vocab_size=cfg.vocab_size, seq_len=16,
                               global_batch=8, objective="clm")
        params, state = transformer.init(jax.random.PRNGKey(0), cfg)
        batch = jax.tree.map(jnp.asarray, data.get_batch(dcfg, step=0))
        grad = jax.jit(jax.value_and_grad(
            lambda p, s, b: transformer.loss_fn(p, s, b, cfg,
                                                train=True)[0]))
        with jax.default_matmul_precision("highest"):
            loss1, g1 = grad(params, state, batch)
            mesh = make_mesh({mesh_shape}, ("data", "model"))
            context.set_mesh(mesh)
            grad = jax.jit(grad.__wrapped__)
            loss2, g2 = grad(sharding.shard_params(params, mesh), state,
                             batch)
        assert "shard_map" in str(jax.make_jaxpr(grad.__wrapped__)(
            params, state, batch))
        assert abs(float(loss1) - float(loss2)) < 1e-5, (loss1, loss2)
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
            a, b = np.asarray(a), np.asarray(b)
            np.testing.assert_allclose(b, a, rtol=1e-4,
                                       atol=1e-5 * np.abs(a).max() + 1e-12)
        print("mesh == one device")
    """), devices=8)


def test_router_bias_moves_against_the_load():
    cfg = configs.get_smoke_config(MOON)
    params, state = moe.moe_init(KEY, cfg)
    params = _skewed(cfg, params, hot=2)
    x = _x_skew(cfg)
    _, _, new, _ = moe.moe_apply(params, state, x, cfg, train=True)
    _, ids, _ = moe.route(params, state, x.reshape(-1, cfg.d_model), cfg)
    load = np.bincount(np.asarray(ids).ravel(), minlength=cfg.num_experts)
    step = np.asarray(new["router_bias"] - state["router_bias"])
    want = moe.BIAS_STEP * np.sign(load.mean() - load)
    np.testing.assert_allclose(step, want, rtol=1e-6)
    assert step[2] < 0 and step[np.argmin(load)] > 0
    # evaluation leaves the bias alone, and the bias carries no gradient
    assert moe.moe_apply(params, state, x, cfg)[2] is state
    g = jax.grad(lambda s: jnp.sum(moe.moe_apply(params, s, x, cfg)[0]))(
        state)
    assert float(jnp.abs(g["router_bias"]).sum()) == 0.0


def test_layer_plan_puts_leading_dense_layers_first():
    cfg = configs.get_config(MOON)
    assert transformer.layer_plan(cfg) == [("run", 1, "mlp"),
                                           ("run", 26, "moe")]
    cut = configs.with_lram(dataclasses.replace(cfg, num_layers=5), 20, 3)
    assert transformer.layer_plan(cut) == [
        ("run", 1, "mlp"), ("run", 2, "moe"), ("memory", 3, "lram"),
        ("run", 1, "moe")]
    params, state = jax.eval_shape(lambda k: transformer.init(k, cut), KEY)
    segs = params["segments"]
    assert "mlp" in segs["seg0"] and "moe" in segs["seg1"]
    assert segs["seg1"]["moe"]["experts"]["wo"].shape == (2, 64, 1408, 2048)
    assert state["seg1"]["moe"]["router_bias"].shape == (2, 64)
    assert segs["seg2"]["attn"]["wkv_b"]["kernel"].shape == (512, 16 * 256)
    assert set(state) == {"seg1", "seg2", "seg3"}


def test_moonlight_published_sizes():
    cfg = configs.get_config(MOON)
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.vocab_size,
            cfg.d_ff, cfg.moe_intermediate_size) == (27, 2048, 16, 163840,
                                                     11264, 1408)
    assert (cfg.num_experts, cfg.experts_held, cfg.top_k_experts,
            cfg.n_shared_experts, cfg.first_k_dense_replace) == (64, 64, 6,
                                                                 2, 1)
    assert 15.5e9 < cfg.param_count() < 16.5e9, cfg.param_count()
    assert 2.5e9 < cfg.active_param_count() < 3.5e9


def test_serving_refuses_latent_attention():
    from repro.serving.engine import EngineConfig, ServeEngine

    cfg = configs.get_smoke_config(MOON)
    with pytest.raises(ValueError, match="latent attention"):
        ServeEngine(None, None, cfg, EngineConfig(slots=2, max_len=16))
    with pytest.raises(NotImplementedError, match="decode cache"):
        transformer.cache_shapes(cfg, 2, 16)


# ----------------------------------------------- the step vs the reference

@pytest.fixture(scope="module")
def bench_modules():
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(BENCH / "tests"))
    import harness
    import smoke
    import weights

    return harness, smoke, weights


def test_moonlight_step_matches_plain_reference(bench_modules):
    """The smoke Moonlight-with-memory step of `launch/train` against the
    benchmark's plain reference from the same seeded weights: loss,
    gradients, and the parameters after one Adam update."""
    harness, smoke, weights = bench_modules
    conf = smoke.smoke_config("moonlight-16b-a3b-lram")
    pcfg = harness.program_config(conf)
    assert pcfg.attention == "mla" and pcfg.experts_held < pcfg.num_experts
    ref = harness.reference(conf["reference"])
    rng = np.random.default_rng(0)
    tok = rng.integers(0, conf["vocab_size"], (2, 17))
    batch = {"tokens": jnp.asarray(tok[:, :-1], jnp.int32),
             "labels": jnp.asarray(tok[:, 1:], jnp.int32)}
    params, state = weights.make(conf, 11)
    o = conf["optimizer"]
    opt_cfg = optim.OptimConfig(lr=o["lr"], memory_lr_mult=o["memory_lr_mult"],
                                memory_path=o["memory_path"], b1=o["b1"],
                                b2=o["b2"], eps=o["eps"],
                                grad_clip=o["grad_clip"])
    with jax.default_matmul_precision("highest"):
        (loss, (pstate, _)), grads = jax.jit(jax.value_and_grad(
            lambda p: transformer.loss_fn(p, state, batch, pcfg),
            has_aux=True))(params)
        (rloss, rstate), rgrads = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(conf, p, state, batch), has_aux=True))(params)
        step = train_mod.build_train_step(pcfg, opt_cfg)
        new, _, _, _, metrics = step(
            jax.tree.map(jnp.copy, params), optim.adam_init(params), state,
            jnp.zeros(()), batch)
        zeros = jax.tree.map(jnp.zeros_like, params)
        rnew = jax.jit(ref.train_step_fn(conf))(
            params, state, zeros, zeros, jnp.float32(1), batch)[0]
    assert float(loss) == pytest.approx(float(rloss), rel=1e-5)
    assert float(metrics["loss"]) == pytest.approx(float(rloss), rel=1e-5)
    assert int(metrics["moe.routed_copies"]) > 0
    # every leaf within 1e-4 of its own norm: float32 rounding, where a
    # dropped token, a wrong gate or a missed term moves a leaf by far more
    for got, want in ((grads, rgrads), (new, rnew), (pstate, rstate)):
        flat, _ = jax.tree_util.tree_flatten_with_path(want)
        gflat = dict(jax.tree_util.tree_flatten_with_path(got)[0])
        for path, w in flat:
            w = np.asarray(w, np.float64)
            gap = np.linalg.norm(np.asarray(gflat[path], np.float64) - w)
            assert gap <= 1e-4 * max(np.linalg.norm(w), 1e-12), \
                jax.tree_util.keystr(path)
