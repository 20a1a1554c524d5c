"""Attention-layer unit tests: RoPE/M-RoPE, masks, GQA, cache mechanics."""


import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.models import attention, transformer
from repro.models.config import ModelConfig


def test_rope_preserves_norm_and_relative_phase(rng):
    x = jnp.asarray(rng.normal(size=(2, 6, 4, 32)).astype(np.float32))
    pos = jnp.broadcast_to(jnp.arange(6), (2, 6))
    y = attention.apply_rope(x, pos, 1e4)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(y), axis=-1),
        rtol=1e-5,
    )
    # dot products depend only on relative positions
    q = jnp.asarray(rng.normal(size=(1, 8, 1, 32)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 8, 1, 32)).astype(np.float32))
    p0 = jnp.broadcast_to(jnp.arange(8), (1, 8))
    qr0, kr0 = attention.apply_rope(q, p0, 1e4), attention.apply_rope(k, p0, 1e4)
    qr5, kr5 = attention.apply_rope(q, p0 + 5, 1e4), attention.apply_rope(
        k, p0 + 5, 1e4)
    s0 = np.einsum("bshd,bthd->bst", np.asarray(qr0), np.asarray(kr0))
    s5 = np.einsum("bshd,bthd->bst", np.asarray(qr5), np.asarray(kr5))
    np.testing.assert_allclose(s0, s5, rtol=1e-4, atol=1e-4)


def test_mrope_equals_rope_for_uniform_positions(rng):
    """Text tokens have t=h=w: M-RoPE must coincide with plain RoPE."""
    x = jnp.asarray(rng.normal(size=(2, 5, 2, 48)).astype(np.float32))
    pos = jnp.broadcast_to(jnp.arange(5), (2, 5))
    pos3 = jnp.broadcast_to(pos, (3, 2, 5))
    y1 = attention.apply_rope(x, pos, 1e4)
    y2 = attention.apply_mrope(x, pos3, 1e4, (8, 8, 8))
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)


def test_swa_mask_limits_receptive_field():
    m = attention._band_mask(8, 8, causal=True, window=3)
    assert m[5, 5] and m[5, 3] and not m[5, 2]  # window of 3
    assert not m[3, 4]  # causal


def test_gqa_matches_mha_when_kv_repeated(rng):
    """GQA with repeated kv == MHA with those heads duplicated."""
    q = jnp.asarray(rng.normal(size=(1, 6, 4, 8)).astype(np.float32))
    k2 = jnp.asarray(rng.normal(size=(1, 6, 2, 8)).astype(np.float32))
    v2 = jnp.asarray(rng.normal(size=(1, 6, 2, 8)).astype(np.float32))
    out_gqa = attention.dense_attention(q, k2, v2, causal=True)
    k4 = jnp.repeat(k2, 2, axis=2)
    v4 = jnp.repeat(v2, 2, axis=2)
    out_mha = attention.dense_attention(q, k4, v4, causal=True)
    np.testing.assert_allclose(
        np.asarray(out_gqa), np.asarray(out_mha), atol=1e-5
    )


def test_layer_plan_structures():
    cfg = configs.get_config("yi-9b")
    assert transformer.layer_plan(cfg) == [("run", 48, "mlp")]
    cfg = configs.with_lram(cfg, 20)
    plan = transformer.layer_plan(cfg)
    assert plan == [("run", 24, "mlp"), ("memory", 24, "lram"),
                    ("run", 23, "mlp")]
    z = configs.get_config("zamba2-2.7b")
    assert transformer.layer_plan(z) == [("hybrid", 9)]


def test_cache_shapes_swa_window_caps_length():
    cfg = configs.get_config("mixtral-8x7b")
    shapes = transformer.cache_shapes(cfg, batch=2, max_len=32768)
    (shape, _dtype) = shapes["seg0"]["k"]
    assert shape[2] == cfg.window  # ring buffer, not 32768
    yi = configs.get_config("yi-9b")
    shapes = transformer.cache_shapes(yi, batch=2, max_len=32768)
    assert shapes["seg0"]["k"][0][2] == 32768


def test_skip_reasons():
    from repro.configs import shapes as shapes_lib

    assert shapes_lib.skip_reason(configs.get_config("yi-9b"), "long_500k")
    assert shapes_lib.skip_reason(
        configs.get_config("mixtral-8x7b"), "long_500k") is None  # SWA
    assert shapes_lib.skip_reason(
        configs.get_config("mamba2-1.3b"), "long_500k") is None
    assert shapes_lib.skip_reason(
        configs.get_config("yi-9b"), "train_4k") is None


def test_with_lram_paper_block_shape():
    cfg = configs.with_lram(configs.get_config("yi-9b"), 20)
    assert cfg.lram.in_dim == cfg.d_model            # w
    assert cfg.lram.out_dim == 4 * cfg.d_model       # 4w
    assert cfg.lram.m == 64 and cfg.lram.heads == cfg.d_model // 16
