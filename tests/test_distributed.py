"""Distributed behaviour on multi-device host meshes.

These tests need >1 device, so each runs in a SUBPROCESS with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — the flag must
never be set in this process (smoke tests and benches see 1 device).
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str, devices: int = 8, timeout: int = 600):
    script = textwrap.dedent(body)
    env = {
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
        "PYTHONPATH": os.path.join(REPO, "src"),
        "PATH": "/usr/bin:/bin",
        "HOME": os.environ.get("HOME", "/root"),
        "JAX_PLATFORMS": "cpu",
    }
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=timeout,
                         cwd=REPO)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-3000:])
    return out.stdout


def test_sharded_train_step_matches_single_device():
    """The same train step on a 4×2 mesh and on 1 device must produce the
    same loss trajectory — sharding is semantics-preserving."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.data import DataConfig, SyntheticSource
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_mesh
        from repro.model.layers import Runtime
        from repro.optim import make_optimizer, warmup_cosine
        from repro.training.train_step import init_train_state, make_train_step
        from repro.launch.dryrun import state_shardings

        cfg = get_config("granite-3-8b-smoke")
        opt = make_optimizer("adamw")
        src = SyntheticSource(DataConfig(global_batch=8, seq_len=32,
                                         vocab=cfg.vocab, seed=2))

        def run(mesh=None):
            rt = Runtime(activation_dtype=jnp.float32,
                         param_dtype=jnp.float32)
            rules = None
            if mesh is not None:
                rules = shd.make_rules(mesh, "fsdp_tp")
                rt = Runtime(activation_dtype=jnp.float32,
                             param_dtype=jnp.float32,
                             shard_activation=shd.act_sharder(mesh, rules))
            state, axes = init_train_state(cfg, jax.random.PRNGKey(0), opt, rt)
            step = make_train_step(cfg, opt, warmup_cosine(1e-3, 2, 20), rt)
            if mesh is not None:
                st_sh = state_shardings(state, axes, mesh, rules)
                state = jax.device_put(state, st_sh)
                b_sh = shd.batch_shardings(
                    {k: v for k, v in src.batch_at(0).items()}, mesh)
                step = jax.jit(step, in_shardings=(st_sh, b_sh),
                               out_shardings=(st_sh, None))
            else:
                step = jax.jit(step)
            losses = []
            for i in range(4):
                batch = src.batch_at(i)
                if mesh is not None:
                    batch = jax.device_put(batch, b_sh)
                state, m = step(state, batch)
                losses.append(float(m["loss"]))
            return losses

        single = run(None)
        mesh = make_mesh((4, 2), ("data", "model"))
        with mesh:
            sharded = run(mesh)
        np.testing.assert_allclose(single, sharded, rtol=2e-4)
        print("MATCH", single[-1], sharded[-1])
    """)
    assert "MATCH" in out


def test_elastic_restore_onto_smaller_mesh():
    """Checkpoint from a 4×2 mesh restores onto 2×2 (node loss) and the
    loss trajectory continues identically — elastic re-mesh."""
    out = run_sub("""
        import os, tempfile
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.data import DataConfig, SyntheticSource
        from repro.distributed import checkpoint as ckpt
        from repro.distributed import sharding as shd
        from repro.distributed.fault_tolerance import ElasticMeshManager
        from repro.launch.mesh import make_mesh
        from repro.model.layers import Runtime
        from repro.optim import make_optimizer, warmup_cosine
        from repro.training.train_step import init_train_state, make_train_step
        from repro.launch.dryrun import state_shardings

        cfg = get_config("stablelm-1.6b-smoke")
        opt = make_optimizer("adamw")
        src = SyntheticSource(DataConfig(global_batch=8, seq_len=32,
                                         vocab=cfg.vocab, seed=5))
        tmp = tempfile.mkdtemp()

        def build(mesh):
            rules = shd.make_rules(mesh, "fsdp_tp")
            rt = Runtime(activation_dtype=jnp.float32,
                         param_dtype=jnp.float32,
                         shard_activation=shd.act_sharder(mesh, rules))
            state, axes = init_train_state(cfg, jax.random.PRNGKey(0), opt, rt)
            st_sh = state_shardings(state, axes, mesh, rules)
            step = jax.jit(make_train_step(
                cfg, opt, warmup_cosine(1e-3, 2, 20), rt),
                in_shardings=(st_sh, None), out_shardings=(st_sh, None))
            return state, st_sh, step

        mesh8 = make_mesh((4, 2), ("data", "model"))
        with mesh8:
            state, st_sh, step = build(mesh8)
            state = jax.device_put(state, st_sh)
            for i in range(3):
                state, m = step(state, src.batch_at(i))
            ckpt.save(tmp, 3, state)
            ref = state
            for i in range(3, 5):
                ref, mref = step(ref, src.batch_at(i))

        # simulate losing half the cluster: elastic plan picks a 2x2 mesh
        mgr = ElasticMeshManager(model_parallel=2, devices_per_pod=8)
        plan = mgr.plan(4)
        assert plan.shape == (2, 2), plan
        mesh4 = make_mesh(plan.shape, plan.axes)
        with mesh4:
            state4, st_sh4, step4 = build(mesh4)
            restored = ckpt.restore(tmp, 3, state4, st_sh4)
            for i in range(3, 5):
                restored, mres = step4(restored, src.batch_at(i))
        np.testing.assert_allclose(float(mref["loss"]),
                                   float(mres["loss"]), rtol=2e-4)
        print("ELASTIC-OK", float(mref["loss"]), float(mres["loss"]))
    """)
    assert "ELASTIC-OK" in out


def test_serve_step_sharded_matches_reference():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_mesh
        from repro.model import transformer as tf
        from repro.model.layers import Runtime

        cfg = get_config("gemma2-9b-smoke")
        rt0 = Runtime(activation_dtype=jnp.float32, param_dtype=jnp.float32)
        params, axes = tf.init(cfg, jax.random.PRNGKey(0), rt0)
        B, L = 4, 64
        caches = tf.init_cache(cfg, B, L, jnp.float32)
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, 1), 0, cfg.vocab)
        kv_len = jnp.asarray([1, 1, 1, 1], jnp.int32)
        ref, _ = tf.decode_step(cfg, params, toks, caches, kv_len, rt0)

        mesh = make_mesh((2, 4), ("data", "model"))
        rules = shd.make_rules(mesh, "serve")
        rt = Runtime(activation_dtype=jnp.float32, param_dtype=jnp.float32,
                     shard_activation=shd.act_sharder(mesh, rules))
        with mesh:
            p_sh = shd.param_shardings(axes, params, mesh, rules)
            c_sh = shd.cache_shardings(tf.cache_axes(cfg), caches, mesh)
            params_s = jax.device_put(params, p_sh)
            caches_s = jax.device_put(caches, c_sh)
            step = jax.jit(
                lambda p, t, c, k: tf.decode_step(cfg, p, t, c, k, rt),
                in_shardings=(p_sh, None, c_sh, None),
                out_shardings=(None, c_sh))
            out, _ = step(params_s, toks, caches_s, kv_len)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)
        print("SERVE-OK")
    """)
    assert "SERVE-OK" in out


def test_replica_meshes_place_each_replica_on_its_own_device():
    """``make_replica_meshes(dp, 1)`` gives every replica its own device,
    and an engine built on such a one-device mesh keeps its params and
    cache pool there and serves from it."""
    out = run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.launch.mesh import make_replica_meshes
        from repro.model import transformer as tf
        from repro.model.layers import Runtime
        from repro.serving.engine import Request, ServeEngine

        meshes = make_replica_meshes(4, 1)
        devs = [m.devices.flat[0] for m in meshes]
        assert [m.size for m in meshes] == [1] * 4
        assert len(set(devs)) == 4, devs
        cfg = get_config("stablelm-1.6b-smoke")
        rt = Runtime(activation_dtype=jnp.float32, param_dtype=jnp.float32)
        params, _ = tf.init(cfg, jax.random.PRNGKey(0), rt)
        eng = ServeEngine(cfg, params, slots=2, max_len=32, rt=rt,
                          decode_chunk=4, cache_layout="paged",
                          page_size=8, mesh=meshes[2])
        req = Request(rid=0, prompt=np.arange(5, dtype=np.int32),
                      max_new_tokens=3)
        eng.submit(req)
        eng.run()
        assert req.done and len(req.generated) == 3
        for tree in (eng.params, eng.caches):
            for leaf in jax.tree.leaves(tree):
                assert leaf.devices() == {devs[2]}, leaf.devices()
        print("REPLICAS-OK")
    """, devices=4)
    assert "REPLICAS-OK" in out


@pytest.mark.parametrize("platform,dp,tp", [("tpu", 4, 1), ("cpu", 2, 2)])
def test_replica_groups_refuse_to_stack_on_shared_devices(platform, dp, tp):
    """Too few accelerator devices for ``dp`` replicas raises instead of
    stacking replicas on one chip; only tp=1 on CPU devices may share."""
    from types import SimpleNamespace

    from repro.distributed.sharding import replica_device_groups

    devs = [SimpleNamespace(platform=platform, id=i) for i in range(2)]
    with pytest.raises(ValueError, match="needs"):
        replica_device_groups(dp, tp, devices=devs)
    cpu = [SimpleNamespace(platform="cpu", id=0)]
    assert replica_device_groups(3, 1, devices=cpu) == [cpu] * 3
