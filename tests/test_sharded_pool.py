"""Device-sharded paged KV pool (PR 5).

The pool's page arrays shard along the kv-head (GQA) / latent-rank (MLA)
axis over a 1-axis "model" mesh; block tables and the prefix index stay
replicated host-side.  The contract under test: greedy token streams from
a sharded engine are BIT-IDENTICAL to the single-device paged engine —
admission, growth, COW, preemption and prefix matching included — and
per-device resident bytes are exactly total/tp.

Multi-device tests run in a SUBPROCESS with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (same pattern as
``test_distributed.py``); divisibility validation is pure host logic and
runs in-process.
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_COMMON = """
    import numpy as np, jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.model import transformer as tf
    from repro.model.layers import Runtime
    from repro.launch.mesh import make_mesh
    from repro.serving.engine import Request, ServeEngine

    rt = Runtime(activation_dtype=jnp.float32, param_dtype=jnp.float32)

    def serve(cfg, params, mesh, plens, new_tokens=5, num_pages=None,
              prefill_chunk=None, prompts=None, seed=1):
        eng = ServeEngine(cfg, params, slots=2, max_len=64, rt=rt,
                          decode_chunk=8, prefill_chunk=prefill_chunk,
                          cache_layout="paged", page_size=8,
                          num_pages=num_pages, mesh=mesh)
        rng = np.random.default_rng(seed)
        reqs = []
        for rid, pl in enumerate(plens):
            prompt = rng.integers(0, cfg.vocab, size=(pl,)).astype(np.int32) \\
                if prompts is None else prompts[rid]
            r = Request(rid=rid, prompt=prompt, max_new_tokens=new_tokens)
            reqs.append(r)
            eng.submit(r)
        eng.run()
        return [list(r.generated) for r in reqs], eng
"""


def run_sub(body: str, devices: int = 4, timeout: int = 900):
    script = textwrap.dedent(_COMMON) + textwrap.dedent(body)
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


def test_sharded_gqa_matches_unsharded_with_prefix_and_cow():
    """stablelm (4 kv heads) on tp=4: identical greedy streams with the
    prefix cache live (shared-prefix hits + a page-aligned COW admission),
    and per-device bytes exactly 1/4 of the pool totals."""
    out = run_sub("""
        cfg = get_config("stablelm-1.6b-smoke")
        params, _ = tf.init(cfg, jax.random.PRNGKey(0), rt)
        rng = np.random.default_rng(0)
        shared = rng.integers(0, cfg.vocab, size=(16,)).astype(np.int32)
        prompts = [np.concatenate(
            [shared, rng.integers(0, cfg.vocab, size=(4 + i,))]
        ).astype(np.int32) for i in range(4)]
        # request 4 re-sends request 0's full prompt after it completes:
        # its prompt exactly covers resident full pages -> COW admission
        prompts.append(prompts[0].copy())
        plens = [len(p) for p in prompts]

        o0, e0 = serve(cfg, params, None, plens, prompts=prompts)
        mesh = make_mesh((4,), ("model",))
        o1, e1 = serve(cfg, params, mesh, plens, prompts=prompts)

        assert o0 == o1, (o0, o1)
        for e in (e0, e1):
            assert e.stats["prefix_hits"] >= 3, e.stats
            assert e.stats["tokens_reused"] >= 3 * 16, e.stats
        # every count agrees; the host_ns_* phase timings are wall time
        counts = [k for k in e0.stats if not k.startswith("host_ns_")]
        assert {k: e0.stats[k] for k in counts} == \\
            {k: e1.stats[k] for k in counts}, (e0.stats, e1.stats)

        m0, m1 = e0.memory_stats(), e1.memory_stats()
        assert m0["sharding"] is None
        sh = m1["sharding"]
        assert sh["tp"] == 4 and sh["axis"] == "model"
        for k in ("resident_cache_bytes", "peak_resident_cache_bytes",
                  "physical_cache_bytes"):
            assert sh["per_device"][k] * 4 == m1[k], (k, sh, m1[k])
        assert m0["peak_resident_cache_bytes"] == \\
            m1["peak_resident_cache_bytes"]
        # the physical page shard on device 0 really is 1/4 of the array
        leaf = e1.caches[0][0]["attn"]["k_pages"]
        local = leaf.addressable_shards[0].data
        assert local.size * 4 == leaf.size, (local.shape, leaf.shape)
        print("GQA-SHARDED-OK", e1.stats["cow_copies"])
    """)
    assert "GQA-SHARDED-OK" in out


def test_sharded_mla_matches_unsharded():
    """deepseek smoke (MLA + MoE) on tp=4: latent pages shard on the rank
    axis AND decode FLOPs shard split-K-parallel (each device sweeps its
    1/tp strip of block-table pages; partials combine with the
    associative running-max algebra) — greedy streams identical."""
    out = run_sub("""
        cfg = get_config("deepseek-v3-671b-smoke")
        params, _ = tf.init(cfg, jax.random.PRNGKey(0), rt)
        plens = (12, 20, 9, 17)
        o0, e0 = serve(cfg, params, None, plens)
        o1, e1 = serve(cfg, params, make_mesh((4,), ("model",)), plens)
        assert o0 == o1, (o0, o1)
        sh = e1.memory_stats()["sharding"]
        assert sh["tp"] == 4
        assert sh["per_device"]["physical_cache_bytes"] * 4 == \\
            e1.memory_stats()["physical_cache_bytes"]
        print("MLA-SHARDED-OK")
    """)
    assert "MLA-SHARDED-OK" in out


def test_sharded_mla_chunked_prefill_matches_unsharded():
    """deepseek smoke with chunked prefill on tp=4: the absorbed-form
    chunk continuation (latent prefix all-gathered to full rank inside
    the mapped region) must reproduce the unsharded streams; a table
    width the mesh does not divide must be refused up front (the
    split-K decode sweeps contiguous per-device page strips)."""
    out = run_sub("""
        cfg = get_config("deepseek-v3-671b-smoke")
        params, _ = tf.init(cfg, jax.random.PRNGKey(0), rt)
        mesh = make_mesh((4,), ("model",))
        plens = (12, 20, 9, 17)
        o0, e0 = serve(cfg, params, None, plens, prefill_chunk=8)
        o1, e1 = serve(cfg, params, mesh, plens, prefill_chunk=8)
        assert o0 == o1, (o0, o1)
        try:
            # max_len 40 / page_size 8 -> 5-page table, not divisible by 4
            ServeEngine(cfg, params, slots=2, max_len=40, rt=rt,
                        cache_layout="paged", page_size=8, mesh=mesh)
            raise SystemExit("indivisible table width did not raise")
        except ValueError as e:
            assert "table width" in str(e), str(e)
        print("MLA-CHUNKED-SHARDED-OK")
    """)
    assert "MLA-CHUNKED-SHARDED-OK" in out


def test_sharded_windowed_chunked_matches_unsharded():
    """gemma2 smoke (global + sliding-window layers, 2 kv heads) on tp=2,
    with chunked prefill so the ring-band history path runs under
    shard_map too."""
    out = run_sub("""
        cfg = get_config("gemma2-9b-smoke")
        params, _ = tf.init(cfg, jax.random.PRNGKey(0), rt)
        plens = (20, 11, 27, 14)
        o0, e0 = serve(cfg, params, None, plens, prefill_chunk=8)
        o1, e1 = serve(cfg, params, make_mesh((2,), ("model",)), plens,
                       prefill_chunk=8)
        assert o0 == o1, (o0, o1)
        assert e1.memory_stats()["sharding"]["tp"] == 2
        print("WINDOWED-SHARDED-OK")
    """)
    assert "WINDOWED-SHARDED-OK" in out


def test_sharded_preemption_tiny_pool_matches_unsharded():
    """A 6-page pool forces growth back-pressure and youngest-first
    preemption; the recompute path must replay identically on a sharded
    pool (same preemption count, same streams)."""
    out = run_sub("""
        cfg = get_config("stablelm-1.6b-smoke")
        params, _ = tf.init(cfg, jax.random.PRNGKey(0), rt)
        plens = (20, 21, 22, 23)
        o0, e0 = serve(cfg, params, None, plens, new_tokens=8, num_pages=6)
        o1, e1 = serve(cfg, params, make_mesh((4,), ("model",)), plens,
                       new_tokens=8, num_pages=6)
        assert o0 == o1, (o0, o1)
        assert e0.stats["preemptions"] == e1.stats["preemptions"] > 0, \\
            (e0.stats, e1.stats)
        print("PREEMPT-SHARDED-OK", e1.stats["preemptions"])
    """)
    assert "PREEMPT-SHARDED-OK" in out


def test_sharded_swap_tier_matches_unsharded():
    """Host swap tier on a tp=2 sharded pool: demotion snapshots the
    sharded page leaves, promotion rebuilds them under the pool's
    sharding constraints — demote→promote→hit streams match the
    unsharded swap engine exactly, with the same swap counters."""
    out = run_sub("""
        cfg = get_config("stablelm-1.6b-smoke")
        params, _ = tf.init(cfg, jax.random.PRNGKey(0), rt)
        rng = np.random.default_rng(9)
        pa = rng.integers(0, cfg.vocab, 24).astype(np.int32)
        pb = rng.integers(0, cfg.vocab, 40).astype(np.int32)

        def serve_swap(mesh):
            eng = ServeEngine(cfg, params, slots=2, max_len=64, rt=rt,
                              decode_chunk=4, cache_layout="paged",
                              page_size=8, num_pages=8,
                              host_swap_bytes=1 << 30, mesh=mesh)
            streams = []
            for rid, p in enumerate((pa, pb, pa)):
                r = Request(rid=rid, prompt=p, max_new_tokens=4)
                eng.submit(r)
                eng.run()
                streams.append(list(r.generated))
            return streams, eng

        o0, e0 = serve_swap(None)
        o1, e1 = serve_swap(make_mesh((2,), ("model",)))
        assert o0 == o1, (o0, o1)
        for e in (e0, e1):
            assert e.kv.stats["demotions"] >= 3, e.kv.stats
            assert e.kv.stats["promotions"] >= 3, e.kv.stats
        assert e0.kv.stats == e1.kv.stats, (e0.kv.stats, e1.kv.stats)
        # promoted page leaves keep the pool's sharding
        leaf = e1.caches[0][0]["attn"]["k_pages"]
        local = leaf.addressable_shards[0].data
        assert local.size * 2 == leaf.size, (local.shape, leaf.shape)
        print("SWAP-SHARDED-OK", e1.kv.stats["promotions"])
    """, devices=2)
    assert "SWAP-SHARDED-OK" in out


def test_uneven_axis_engine_raises():
    """granite smoke has a single kv head: a tp=4 mesh cannot shard it —
    the engine must refuse up front (never silently replicate), and the
    dense layout must refuse a mesh outright."""
    out = run_sub("""
        cfg = get_config("granite-3-8b-smoke")
        params, _ = tf.init(cfg, jax.random.PRNGKey(0), rt)
        mesh = make_mesh((4,), ("model",))
        try:
            ServeEngine(cfg, params, slots=2, max_len=64, rt=rt,
                        cache_layout="paged", page_size=8, mesh=mesh)
            raise SystemExit("uneven kv-head sharding did not raise")
        except ValueError as e:
            assert "n_kv_heads=1" in str(e) and "tp=4" in str(e), str(e)
        try:
            ServeEngine(cfg, params, slots=2, max_len=64, rt=rt,
                        cache_layout="dense", mesh=mesh)
            raise SystemExit("dense + mesh did not raise")
        except ValueError as e:
            assert "paged" in str(e), str(e)
        print("UNEVEN-RAISES-OK")
    """)
    assert "UNEVEN-RAISES-OK" in out


def test_validate_kv_shard_divisibility():
    """Pure host logic — no devices needed: the validator accepts exactly
    the (config, tp) pairs whose kv-head / latent axes divide."""
    from repro.configs import get_config
    from repro.distributed.sharding import validate_kv_shard

    validate_kv_shard(get_config("stablelm-1.6b-smoke"), 4)   # 4 kv heads
    validate_kv_shard(get_config("gemma2-9b-smoke"), 2)       # 2 kv heads
    validate_kv_shard(get_config("deepseek-v3-671b-smoke"), 4)  # r=32 rd=16
    validate_kv_shard(get_config("granite-3-8b-smoke"), 1)    # tp=1 no-op

    with pytest.raises(ValueError, match="n_kv_heads=1"):
        validate_kv_shard(get_config("granite-3-8b-smoke"), 4)
    with pytest.raises(ValueError, match="n_kv_heads=2"):
        validate_kv_shard(get_config("gemma2-9b-smoke"), 4)
    with pytest.raises(ValueError, match="kv_lora_rank"):
        validate_kv_shard(get_config("deepseek-v3-671b-smoke"), 3)
