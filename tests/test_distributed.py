"""Multi-device behaviour (subprocess with 8 fake devices): sharded LRAM
lookup, pipeline parallelism, compressed collectives, sharded-vs-single
train-step equivalence, elastic checkpoint reshape, fault monitor."""

import pytest

import textwrap


from conftest import run_in_subprocess

from repro.distributed import fault


# ---------------------------------------------------------------------------
# in-process: fault-tolerance units (no devices needed)
# ---------------------------------------------------------------------------

def test_heartbeat_monitor_flags_stragglers():
    mon = fault.HeartbeatMonitor(num_hosts=4)
    for step in range(10):
        for h in range(4):
            mon.heartbeat(h, 1.0 if h != 2 else 3.0, now=float(step))
    assert mon.stragglers() == [2]
    assert mon.healthy(now=10.0)


def test_heartbeat_monitor_detects_dead_host():
    mon = fault.HeartbeatMonitor(num_hosts=3, timeout_s=5.0)
    mon.heartbeat(0, 1.0, now=0.0)
    mon.heartbeat(1, 1.0, now=0.0)
    # host 2 never reports; hosts 0/1 keep reporting
    mon.heartbeat(0, 1.0, now=6.0)
    mon.heartbeat(1, 1.0, now=6.0)
    assert mon.dead_hosts(now=7.0) == [2]


def test_step_timer_outliers():
    t = fault.StepTimer()
    for _ in range(20):
        t.record(0.1)
    assert t.is_outlier(0.5)
    assert not t.is_outlier(0.15)


# ---------------------------------------------------------------------------
# subprocess: 8 fake devices
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sharded_lram_lookup_matches_reference():
    run_in_subprocess(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core import indexing, lram
        from repro.distributed.sharded_lram import sharded_gather_interp
        from repro.kernels import ref

        mesh = make_mesh((2, 4), ("data", "model"))
        spec = indexing.choose_torus(16)
        rng = np.random.default_rng(0)
        values = jnp.asarray(rng.normal(size=(spec.num_locations, 16))
                             .astype(np.float32))
        q = jnp.asarray(rng.uniform(0, 8, size=(8, 3, 8)).astype(np.float32))
        idx, w = lram.indices_and_weights(q, spec, 32)
        want = ref.gather_interp_ref(values, idx, w)
        interp = sharded_gather_interp(mesh, axis="model")
        got = interp(values, idx, w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

        # gradients flow through the shard_map path
        def loss(v):
            return jnp.sum(interp(v, idx, w) ** 2)
        g = jax.grad(loss)(values)
        def loss_ref(v):
            return jnp.sum(ref.gather_interp_ref(v, idx, w) ** 2)
        g_ref = jax.grad(loss_ref)(values)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   rtol=1e-4, atol=1e-4)
        print("sharded lram OK")
    """), devices=8)


@pytest.mark.slow
def test_pipeline_parallel_matches_sequential():
    run_in_subprocess(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.distributed.pipeline import pipeline_apply

        mesh = make_mesh((4,), ("pod",))
        rng = np.random.default_rng(0)
        n_stages, d = 4, 16
        Ws = jnp.asarray(rng.normal(size=(n_stages, d, d))
                         .astype(np.float32) * 0.3)
        x = jnp.asarray(rng.normal(size=(8, d)).astype(np.float32))

        def stage(W, x):
            return jnp.tanh(x @ W)

        seq = x
        for i in range(n_stages):
            seq = stage(Ws[i], seq)
        out = pipeline_apply(stage, Ws, x, mesh=mesh, axis="pod",
                             num_microbatches=4)
        np.testing.assert_allclose(np.asarray(out), np.asarray(seq),
                                   rtol=1e-5, atol=1e-5)
        print("pipeline OK")
    """), devices=4)


@pytest.mark.slow
def test_compressed_psum_close_to_exact():
    run_in_subprocess(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.distributed.collectives import compressed_psum

        mesh = make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(8, 64)).astype(np.float32))

        def f(xl):
            return compressed_psum(xl, "data")

        out = shard_map(f, mesh=mesh, in_specs=(P("data", None),),
                        out_specs=P(None))(x)
        exact = x.sum(0)
        err = np.abs(np.asarray(out[0]) - np.asarray(exact)).max()
        scale = float(jnp.abs(x).max()) / 127.0
        assert err <= 8 * scale + 1e-6, (err, scale)
        print("compressed psum OK, err", err)
    """), devices=8)


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    run_in_subprocess(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro import configs, data, optim
        from repro.distributed import sharding
        from repro.launch.train import build_train_step
        from repro.models import transformer

        cfg = configs.get_smoke_config("yi-9b")
        dcfg = data.DataConfig(vocab_size=cfg.vocab_size, seq_len=16,
                               global_batch=8, objective="clm")
        opt_cfg = optim.OptimConfig(lr=1e-3)
        key = jax.random.PRNGKey(0)
        params, mstate = transformer.init(key, cfg)
        batch = jax.tree.map(jnp.asarray, data.get_batch(dcfg, step=0))

        # single device (donates params -> re-init below for the mesh path)
        step1 = build_train_step(cfg, opt_cfg)
        p1, o1, _, _, m1 = step1(params, optim.adam_init(params), mstate,
                                  jnp.zeros(()), batch)

        # sharded over 4x2 mesh (same PRNG key -> identical init)
        params2, _ = transformer.init(key, cfg)
        mesh = make_mesh((4, 2), ("data", "model"))
        ps = sharding.shard_params(params2, mesh)
        stepm = build_train_step(cfg, opt_cfg, mesh)
        p2, o2, _, _, m2 = stepm(ps, optim.adam_init(ps), mstate,
                                  jnp.zeros(()), batch)
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
        diff = jax.tree.map(
            lambda a, b: float(jnp.abs(a.astype(jnp.float32)
                                        - b.astype(jnp.float32)).max()),
            p1, p2)
        worst = max(jax.tree.leaves(diff))
        assert worst < 5e-3, worst
        print("sharded == single-device OK, worst", worst)
    """), devices=8)


@pytest.mark.slow
def test_elastic_checkpoint_reshape():
    run_in_subprocess(textwrap.dedent("""
        import tempfile
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import CheckpointManager
        from repro.distributed import sharding

        tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        d = tempfile.mkdtemp()
        mgr = CheckpointManager(d)

        mesh_a = make_mesh((4, 2), ("data", "model"))
        sharded = jax.device_put(
            tree["w"], NamedSharding(mesh_a, P("data", "model")))
        mgr.save(1, {"w": sharded})

        # restore onto a DIFFERENT mesh shape (elastic rescale 8 -> 2x4)
        mesh_b = make_mesh((2, 4), ("data", "model"))
        target = NamedSharding(mesh_b, P("model", "data"))
        step, restored = mgr.restore({"w": tree["w"]},
                                     sharding={"w": target})
        assert step == 1
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(tree["w"]))
        assert restored["w"].sharding == target
        print("elastic reshape OK")
    """), devices=8)


@pytest.mark.slow
def test_train_driver_failure_and_resume(tmp_path):
    """Kill the driver mid-run via injected failure; relaunch resumes from
    the checkpoint and finishes."""
    code = textwrap.dedent(f"""
        import sys
        from repro.distributed.fault import SimulatedFailure
        from repro.launch import train
        args = ["--arch", "lram-bert-baseline", "--smoke", "--steps", "12",
                "--batch", "2", "--seq", "32", "--ckpt-dir",
                r"{tmp_path}", "--ckpt-every", "4", "--log-every", "4"]
        try:
            train.main(args + ["--simulate-failure-at", "9"])
            raise SystemExit("expected SimulatedFailure")
        except SimulatedFailure:
            print("crashed as requested")
        train.main(args)  # relaunch: must resume from step 8 and finish
        print("resumed-and-finished")
    """)
    out = run_in_subprocess(code, devices=1, timeout=900)
    assert "crashed as requested" in out
    assert "resumed from step 8" in out
    assert "resumed-and-finished" in out
