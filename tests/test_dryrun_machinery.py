"""Dry-run machinery on a small mesh (subprocess; full pipeline but smoke
configs): lower + compile + cost/memory/collective extraction must work for
every mode (train / prefill / decode) and both mesh layouts."""

import pytest

import textwrap

from conftest import run_in_subprocess


@pytest.mark.slow
def test_lower_compile_and_analyze_all_modes():
    run_in_subprocess(textwrap.dedent("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import configs, optim
        from repro.analysis import hlo as hlo_lib
        from repro.configs import shapes as shapes_lib
        from repro.distributed import sharding
        from repro.models import transformer

        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = configs.get_smoke_config("yi-9b")
        params, state = jax.eval_shape(
            lambda: transformer.init(jax.random.PRNGKey(0), cfg))
        pspecs = sharding.param_pspecs(params, mesh)
        p_in = jax.tree.map(
            lambda sd, sp: jax.ShapeDtypeStruct(
                sd.shape, sd.dtype, sharding=NamedSharding(mesh, sp)),
            params, pspecs)
        s_in = jax.tree.map(
            lambda sd: jax.ShapeDtypeStruct(
                sd.shape, sd.dtype, sharding=NamedSharding(mesh, P())),
            state)

        # ---- train ----
        opt_cfg = optim.OptimConfig()
        def train_step(p, o, s, batch):
            (l, (ns, m)), g = jax.value_and_grad(
                transformer.loss_fn, has_aux=True)(p, s, batch, cfg)
            np_, no, st = optim.adam_update(g, o, p, opt_cfg)
            return np_, no, ns, l
        opt_sh = jax.eval_shape(optim.adam_init, params)
        o_in = {"mu": p_in and jax.tree.map(
                    lambda sd, sp: jax.ShapeDtypeStruct(
                        sd.shape, jnp.float32,
                        sharding=NamedSharding(mesh, sp)),
                    params, pspecs),
                "nu": jax.tree.map(
                    lambda sd, sp: jax.ShapeDtypeStruct(
                        sd.shape, jnp.float32,
                        sharding=NamedSharding(mesh, sp)),
                    params, pspecs),
                "step": jax.ShapeDtypeStruct((), jnp.int32,
                        sharding=NamedSharding(mesh, P()))}
        batch = {k: jax.ShapeDtypeStruct((8, 32), jnp.int32,
                 sharding=NamedSharding(mesh, P("data")))
                 for k in ("tokens", "labels")}
        def costd(c):  # newer jaxlib returns [dict]
            cost = c.cost_analysis()
            return cost[0] if isinstance(cost, list) else cost
        c = jax.jit(train_step).lower(p_in, o_in, s_in, batch).compile()
        cost = costd(c)
        assert cost.get("flops", 0) > 0
        coll = hlo_lib.parse_collectives(c.as_text())
        assert coll.counts, "expected collectives in the sharded step"
        assert coll.total_wire_bytes > 0
        print("train OK", cost.get("flops"), coll.counts)

        # ---- decode ----
        cache_sh = transformer.cache_specs(cfg, 8, 64)
        cspec = sharding.cache_pspecs(cache_sh, cfg, mesh)
        c_in = jax.tree.map(
            lambda sd, sp: jax.ShapeDtypeStruct(
                sd.shape, sd.dtype, sharding=NamedSharding(mesh, sp)),
            cache_sh, cspec)
        tok = jax.ShapeDtypeStruct((8, 1), jnp.int32,
                sharding=NamedSharding(mesh, P("data")))
        pos = jax.ShapeDtypeStruct((), jnp.int32,
                sharding=NamedSharding(mesh, P()))
        def serve_step(p, s, t, i, cc):
            return transformer.decode_step(p, s, t, i, cc, cfg)
        c2 = jax.jit(serve_step).lower(p_in, s_in, tok, pos, c_in).compile()
        assert costd(c2).get("flops", 0) > 0
        print("decode OK")

        # ---- multi-pod-style 3-axis mesh ----
        mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
        pspecs3 = sharding.param_pspecs(params, mesh3)
        p3 = jax.tree.map(
            lambda sd, sp: jax.ShapeDtypeStruct(
                sd.shape, sd.dtype, sharding=NamedSharding(mesh3, sp)),
            params, pspecs3)
        s3 = jax.tree.map(
            lambda sd: jax.ShapeDtypeStruct(
                sd.shape, sd.dtype, sharding=NamedSharding(mesh3, P())),
            state)
        b3 = {k: jax.ShapeDtypeStruct((8, 32), jnp.int32,
              sharding=NamedSharding(mesh3, P(("pod", "data"))))
              for k in ("tokens", "labels")}
        def fwd(p, s, b):
            return transformer.loss_fn(p, s, b, cfg)[0]
        c3 = jax.jit(fwd).lower(p3, s3, b3).compile()
        assert costd(c3).get("flops", 0) > 0
        print("multi-pod-mesh OK")
    """), devices=8, timeout=900)


def test_hlo_collective_parser_units():
    from repro.analysis import hlo as hlo_lib

    text = """
  %ag = bf16[8,128]{1,0} all-gather(%p0), replica_groups={{0,1,2,3}}, dimensions={0}
  %ar = f32[256]{0} all-reduce(%x), replica_groups=[32,16]<=[512], to_apply=%sum
  %rs = f32[64]{0} reduce-scatter(%y), replica_groups={{0,1}}, dimensions={0}
  %cp = bf16[4,4]{1,0} collective-permute(%z), source_target_pairs={{0,1}}
  %ars = (f32[128]{0}, f32[128]{0}) all-reduce-start(%w), replica_groups={{0,1,2,3}}
"""
    st = hlo_lib.parse_collectives(text)
    assert st.counts == {"all-gather": 1, "all-reduce": 2,
                         "reduce-scatter": 1, "collective-permute": 1}
    # all-gather: (4-1)/4 * 8*128*2 bytes
    assert abs(st.wire_bytes["all-gather"] - 0.75 * 2048) < 1e-6
    # all-reduce: 2*(16-1)/16 * 1024 + async one: 2*(4-1)/4*512
    assert abs(st.wire_bytes["all-reduce"]
               - (2 * 15 / 16 * 1024 + 2 * 0.75 * 512)) < 1e-6
    # reduce-scatter: (2-1) * 256
    assert abs(st.wire_bytes["reduce-scatter"] - 256) < 1e-6
    assert abs(st.wire_bytes["collective-permute"] - 32) < 1e-6
