"""Serving engine: continuous batching produces step-consistent tokens."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.model import transformer as tf
from repro.model.layers import Runtime
from repro.serving.engine import Request, ServeEngine, assert_no_recompiles

RT = Runtime(activation_dtype=jnp.float32, param_dtype=jnp.float32)


def test_engine_matches_manual_greedy_decode():
    cfg = get_config("stablelm-1.6b-smoke")
    params, _ = tf.init(cfg, jax.random.PRNGKey(0), RT)
    prompt = np.asarray([5, 9, 2, 11, 3], np.int32)
    n_new = 6

    # manual reference: single-sequence greedy decode
    caches = tf.init_cache(cfg, 1, 64, jnp.float32)
    toks = []
    kv = 0
    logits = None
    for t in prompt:
        kv += 1
        logits, caches = tf.decode_step(
            cfg, params, jnp.asarray([[t]]), caches,
            jnp.asarray([kv], jnp.int32), RT)
    for _ in range(n_new):
        nxt = int(jnp.argmax(logits[0]))
        toks.append(nxt)
        kv += 1
        logits, caches = tf.decode_step(
            cfg, params, jnp.asarray([[nxt]]), caches,
            jnp.asarray([kv], jnp.int32), RT)

    engine = ServeEngine(cfg, params, slots=2, max_len=64, rt=RT)
    req = Request(rid=0, prompt=prompt, max_new_tokens=n_new)
    engine.submit(req)
    engine.run()
    assert req.done
    assert req.generated == toks


def test_engine_handles_more_requests_than_slots():
    cfg = get_config("stablelm-1.6b-smoke")
    params, _ = tf.init(cfg, jax.random.PRNGKey(0), RT)
    engine = ServeEngine(cfg, params, slots=2, max_len=32, rt=RT)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, 4).astype(np.int32),
                    max_new_tokens=3) for i in range(5)]
    for r in reqs:
        engine.submit(r)
    engine.run()
    assert all(r.done for r in reqs)
    assert all(len(r.generated) == 3 for r in reqs)


def test_dispatch_counts_are_batched_not_per_token():
    """The fast path's contract: prefill dispatches independent of prompt
    length; decode dispatches ≪ decoded tokens (fused multi-step loop)."""
    cfg = get_config("stablelm-1.6b-smoke")
    params, _ = tf.init(cfg, jax.random.PRNGKey(0), RT)
    rng = np.random.default_rng(0)

    counts = {}
    for plen in (4, 24):
        engine = ServeEngine(cfg, params, slots=2, max_len=64, rt=RT)
        for i in range(2):
            engine.submit(Request(
                rid=i, prompt=rng.integers(0, cfg.vocab, plen).astype(np.int32),
                max_new_tokens=8))
        engine.run()
        counts[plen] = dict(engine.stats)

    for plen, st in counts.items():
        assert st["tokens_decoded"] == 16
        assert st["decode_dispatches"] < st["tokens_decoded"], \
            f"per-token decode dispatches at prompt_len={plen}: {st}"
    assert counts[4]["prefill_dispatches"] == counts[24]["prefill_dispatches"]


def test_engine_mixed_prompt_lengths_and_budgets():
    cfg = get_config("stablelm-1.6b-smoke")
    params, _ = tf.init(cfg, jax.random.PRNGKey(0), RT)
    engine = ServeEngine(cfg, params, slots=3, max_len=64, rt=RT,
                         decode_chunk=4)
    rng = np.random.default_rng(1)
    lens = [3, 9, 5, 7]
    buds = [2, 7, 4, 1]
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, l).astype(np.int32),
                    max_new_tokens=b) for i, (l, b) in enumerate(zip(lens, buds))]
    for r in reqs:
        engine.submit(r)
    engine.run()
    assert all(r.done for r in reqs)
    assert [len(r.generated) for r in reqs] == buds
    assert all(r.ttft is not None and r.ttft >= 0 for r in reqs)


def test_engine_temperature_sampling_runs():
    cfg = get_config("stablelm-1.6b-smoke")
    params, _ = tf.init(cfg, jax.random.PRNGKey(0), RT)
    engine = ServeEngine(cfg, params, slots=2, max_len=32, rt=RT,
                         temperature=0.8)
    rng = np.random.default_rng(2)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, 4).astype(np.int32),
                    max_new_tokens=5) for i in range(2)]
    for r in reqs:
        engine.submit(r)
    engine.run()
    assert all(r.done and len(r.generated) == 5 for r in reqs)
    assert all(0 <= t < cfg.vocab for r in reqs for t in r.generated)


def test_prefill_jit_keys_are_length_bucketed():
    """PR-2 follow-up: prompts are padded to power-of-two buckets (masked
    SSM stepping + masked ring/page writes), so a fresh prompt length
    inside an already-seen bucket must NOT trigger a fresh prefill
    compile — the jit key is (group width, bucket)."""
    cfg = get_config("stablelm-1.6b-smoke")
    params, _ = tf.init(cfg, jax.random.PRNGKey(0), RT)
    engine = ServeEngine(cfg, params, slots=2, max_len=64, rt=RT)
    rng = np.random.default_rng(4)

    def serve_len(plen):
        req = Request(rid=plen,
                      prompt=rng.integers(0, cfg.vocab, plen).astype(
                          np.int32),
                      max_new_tokens=2)
        engine.submit(req)
        engine.run()
        assert req.done
        return req

    serve_len(5)
    keys_after_first = set(engine._prefill_fns)
    serve_len(7)                       # same bucket (8) → no new key
    assert set(engine._prefill_fns) == keys_after_first == {(1, 8, 0)}
    serve_len(9)                       # next bucket (16) → one new key
    assert set(engine._prefill_fns) == {(1, 8, 0), (1, 16, 0)}

    # bucketing must not perturb the greedy stream: same prompt through a
    # bucketed engine and via the manual per-token reference path
    prompt = np.asarray([5, 9, 2, 11, 3], np.int32)
    caches = tf.init_cache(cfg, 1, 64, jnp.float32)
    kv, logits = 0, None
    for t in prompt:
        kv += 1
        logits, caches = tf.decode_step(
            cfg, params, jnp.asarray([[t]]), caches,
            jnp.asarray([kv], jnp.int32), RT)
    toks = []
    for _ in range(4):
        nxt = int(jnp.argmax(logits[0]))
        toks.append(nxt)
        kv += 1
        logits, caches = tf.decode_step(
            cfg, params, jnp.asarray([[nxt]]), caches,
            jnp.asarray([kv], jnp.int32), RT)
    req = Request(rid=99, prompt=prompt, max_new_tokens=4)
    engine.submit(req)
    engine.run()
    assert req.generated == toks


def test_warmed_engine_serves_without_recompiles():
    """The warmup guarantee (paged + prefix engine): after ``warmup`` has
    compiled every jit key the workload's length buckets can produce,
    real traffic of those lengths — cold prompts AND an identical resend
    through the prefix-hit path — triggers zero jit retraces.  A length
    from an *unwarmed* bucket must trip the detector (it is not
    vacuous)."""
    cfg = get_config("stablelm-1.6b-smoke")
    params, _ = tf.init(cfg, jax.random.PRNGKey(0), RT)
    engine = ServeEngine(cfg, params, slots=2, max_len=64, rt=RT,
                         decode_chunk=4, cache_layout="paged",
                         page_size=16, prefix_caching=True)
    engine.warmup([5, 9])
    rng = np.random.default_rng(6)

    def serve(prompts):
        reqs = [Request(rid=i, prompt=p, max_new_tokens=4)
                for i, p in enumerate(prompts)]
        for r in reqs:
            engine.submit(r)
        engine.run()
        assert all(r.done for r in reqs)

    p5 = rng.integers(0, cfg.vocab, 5).astype(np.int32)
    p9 = rng.integers(0, cfg.vocab, 9).astype(np.int32)
    with assert_no_recompiles():
        serve([p5, p9])        # cold prompts, warmed buckets
        serve([p5, p9])        # identical resend → prefix-hit offsets
    # negative control: bucket 32 was never warmed → must be detected
    with pytest.raises(AssertionError, match="retrace"):
        with assert_no_recompiles():
            serve([rng.integers(0, cfg.vocab, 20).astype(np.int32)])


def test_chunked_prefill_matches_whole_prompt():
    """kv_offset continuation (full + ring/window caches): an engine that
    prefills in chunks emits the same greedy tokens as whole-prompt."""
    for arch in ("stablelm-1.6b-smoke", "gemma2-9b-smoke"):
        cfg = get_config(arch)
        params, _ = tf.init(cfg, jax.random.PRNGKey(0), RT)
        prompt = np.asarray(
            np.random.default_rng(3).integers(0, cfg.vocab, 20), np.int32)

        outs = []
        for chunk in (None, 8):
            engine = ServeEngine(cfg, params, slots=1, max_len=128, rt=RT,
                                 prefill_chunk=chunk)
            req = Request(rid=0, prompt=prompt, max_new_tokens=6)
            engine.submit(req)
            engine.run()
            assert req.done
            outs.append(req.generated)
        assert outs[0] == outs[1], f"arch={arch}: {outs}"


def test_compilation_cache_dir_rule(monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` wins and is left to jax; unset, the
    cache sits at ``<checkout>/.jax_cache`` whether or not the checkout is
    a git repository."""
    import os

    from repro.serving.engine import enable_compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert enable_compilation_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == \
            saved["jax_compilation_cache_dir"]

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(repo, ".jax_cache")
        assert enable_compilation_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
