"""Paged KV-cache subsystem: free-list allocator, block-table growth,
paged↔dense greedy equivalence (mixed lengths, ring eviction, preemption),
and the paged split-K Pallas kernel vs the reference."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import (
    decode_reference, fusemax_decode_paged, gather_kv_pages,
)
from repro.model import transformer as tf
from repro.model.layers import Runtime
from repro.serving.engine import Request, ServeEngine
from repro.serving.kv_cache import PagePool, PagedKVCache

RT = Runtime(activation_dtype=jnp.float32, param_dtype=jnp.float32)


# ---------------------------------------------------------------------------
# host-side allocator + manager
# ---------------------------------------------------------------------------

def test_page_pool_alloc_free_reuse():
    pool = PagePool(4)
    a = pool.alloc(3)
    assert len(a) == 3 and pool.pages_in_use == 3
    assert pool.alloc(2) is None          # insufficient → no change
    assert pool.pages_in_use == 3
    b = pool.alloc(1)
    assert pool.free_pages == 0
    pool.free(a)
    c = pool.alloc(2)                     # freed pages are reusable
    assert set(c) <= set(a)
    assert pool.peak_in_use == 4
    pool.free(b + c)
    assert pool.pages_in_use == 0


def test_paged_kv_cache_grow_release():
    cfg = get_config("stablelm-1.6b-smoke")
    kv = PagedKVCache(cfg, slots=2, max_len=64, dtype=jnp.float32,
                      page_size=16, num_pages=6)
    assert kv.classes["full"].table_width == 4
    assert kv.grow(0, 20)                 # 2 pages
    assert kv.pages_in_use["full"] == 2
    assert kv.grow(0, 20)                 # idempotent: nothing more needed
    assert kv.pages_in_use["full"] == 2
    assert kv.grow(1, 60)                 # 4 pages → pool exactly full
    assert kv.pages_in_use["full"] == 6
    assert not kv.grow(0, 40)             # would need a 3rd page → refused
    assert kv.pages_in_use["full"] == 6   # all-or-nothing: unchanged
    kv.check_invariants()
    tbl = kv.tables()["full"]
    assert tbl.shape == (2, 4)
    # slot 0's two pages and slot 1's four are disjoint
    used = list(np.asarray(tbl)[0, :2]) + list(np.asarray(tbl)[1])
    assert len(set(used)) == 6
    kv.release(1)
    assert kv.pages_in_use["full"] == 2
    assert kv.grow(0, 40)                 # freed pages reusable
    kv.check_invariants()
    # a pool smaller than one worst-case request is rejected up front —
    # the preempt-youngest progress guarantee needs a lone request to fit
    tiny = PagedKVCache(cfg, slots=2, max_len=64, dtype=jnp.float32,
                        page_size=16, num_pages=3)
    with pytest.raises(ValueError):
        tiny.validate_request(64)         # needs 4 pages, pool has 3
    # window class: bounded working set regardless of kv_target
    g2 = get_config("gemma2-9b-smoke")
    kvw = PagedKVCache(g2, slots=1, max_len=128, dtype=jnp.float32,
                       page_size=16)
    w = g2.layer_specs()[0].window
    assert kvw.pages_needed(f"w{w}", 10_000) == -(-w // 16)


# ---------------------------------------------------------------------------
# paged ↔ dense equivalence through the engine
# ---------------------------------------------------------------------------

def _serve(cfg, params, prompts, layout, **kw):
    eng = ServeEngine(cfg, params, slots=2, max_len=64, rt=RT,
                      decode_chunk=4, cache_layout=layout, **kw)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=6)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.done for r in reqs)
    return [list(r.generated) for r in reqs], eng


def test_paged_matches_dense_greedy_mixed_lengths():
    """The acceptance property: a mixed-length trace through the paged
    layout emits bit-identical greedy tokens to the dense layout, while
    resident memory tracks live tokens (pool drains on completion)."""
    cfg = get_config("stablelm-1.6b-smoke")
    params, _ = tf.init(cfg, jax.random.PRNGKey(0), RT)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, l).astype(np.int32)
               for l in (5, 12, 9, 20, 7)]
    dense, de = _serve(cfg, params, prompts, "dense")
    paged, pe = _serve(cfg, params, prompts, "paged", page_size=16)
    assert dense == paged
    assert pe.stats["preemptions"] == 0
    m = pe.memory_stats()
    assert m["resident_cache_bytes"] == 0          # no live slots remain
    assert 0 < m["peak_resident_cache_bytes"] < \
        de.memory_stats()["physical_cache_bytes"]
    pe.kv.check_invariants()
    # completed requests' full pages are retained as reusable prefix
    # cache; dropping the index drains the pool to fully free
    pe.clear_prefix_cache()
    assert all(v == 0 for v in pe.kv.pages_in_use.values())
    pe.kv.check_invariants()


def test_preemption_on_pool_exhaustion_matches_dense():
    """A pool too small for the full working set forces preemptions; the
    recompute-preemption path must reproduce the dense stream exactly and
    return every page to the free list."""
    cfg = get_config("stablelm-1.6b-smoke")
    params, _ = tf.init(cfg, jax.random.PRNGKey(0), RT)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, l).astype(np.int32)
               for l in (11, 16, 6, 14)]
    dense, _ = _serve(cfg, params, prompts, "dense")
    paged, pe = _serve(cfg, params, prompts, "paged",
                       page_size=8, num_pages=5)    # 40 tokens of pool
    assert dense == paged
    assert pe.stats["preemptions"] > 0
    assert any(r > 0 for r in
               pe.memory_stats()["peak_pages_in_use"].values())
    pe.kv.check_invariants()
    pe.clear_prefix_cache()
    assert all(v == 0 for v in pe.kv.pages_in_use.values())
    pe.kv.check_invariants()


def test_paged_ring_eviction_matches_dense_rotation():
    """gemma2 local/global alternation with prompts longer than the
    window: the windowed layers' paged ring (fixed page working set,
    wrap-around addressing) must match the dense rotation path."""
    cfg = get_config("gemma2-9b-smoke")
    params, _ = tf.init(cfg, jax.random.PRNGKey(0), RT)
    rng = np.random.default_rng(2)
    w = cfg.layer_specs()[0].window
    prompts = [rng.integers(0, cfg.vocab, l).astype(np.int32)
               for l in (w + 9, 12)]                # one wraps, one doesn't

    def serve(layout, **kw):
        eng = ServeEngine(cfg, params, slots=2, max_len=128, rt=RT,
                          decode_chunk=4, cache_layout=layout, **kw)
        reqs = [Request(rid=i, prompt=p, max_new_tokens=5)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        eng.run()
        assert all(r.done for r in reqs)
        return [list(r.generated) for r in reqs]

    assert serve("dense") == serve("paged", page_size=16)


# ---------------------------------------------------------------------------
# paged split-K Pallas kernel
# ---------------------------------------------------------------------------

def test_paged_pallas_decode_matches_reference():
    b, hq, hkv, e, f = 2, 4, 2, 16, 16
    n_pages, ps, width = 10, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, hq, 1, e), jnp.float32)
    k_pages = jax.random.normal(ks[1], (n_pages, hkv, ps, e), jnp.float32)
    v_pages = jax.random.normal(ks[2], (n_pages, hkv, ps, f), jnp.float32)
    bt = jnp.asarray([[3, 1, 7, 0], [2, 5, 9, 4]], jnp.int32)
    kv_len = jnp.asarray([13, 29], jnp.int32)

    k = gather_kv_pages(k_pages, bt)
    v = gather_kv_pages(v_pages, bt)
    ref = decode_reference(q, k, v, kv_len)
    for impl in ("jnp", "pallas"):
        out = fusemax_decode_paged(q, k_pages, v_pages, bt, kv_len,
                                   impl=impl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5,
                                   err_msg=f"impl={impl}")


def test_paged_pallas_decode_splits_and_softcap():
    b, hq, hkv, e = 1, 8, 4, 32
    n_pages, ps, width = 12, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (b, hq, 1, e), jnp.float32)
    k_pages = jax.random.normal(ks[1], (n_pages, hkv, ps, e), jnp.float32)
    v_pages = jax.random.normal(ks[2], (n_pages, hkv, ps, e), jnp.float32)
    bt = jax.random.permutation(ks[3], n_pages)[:width][None].astype(
        jnp.int32)
    kv_len = jnp.asarray([77], jnp.int32)
    k = gather_kv_pages(k_pages, bt)
    v = gather_kv_pages(v_pages, bt)
    ref = decode_reference(q, k, v, kv_len, softcap=30.0)
    out = fusemax_decode_paged(q, k_pages, v_pages, bt, kv_len,
                               softcap=30.0, impl="pallas", splits=4,
                               block_k=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def _quantized_pool(key, shape, qdt):
    """Codes [L, P, Hkv, ps, E] and fp16 scales [L, P, Hkv, ps] of random
    K/V, as a quantized pool stores them."""
    from repro.model.attention import quantize_kv
    return quantize_kv(jax.random.normal(key, shape, jnp.float32), qdt)


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
@pytest.mark.parametrize("pool", ["bf16", "int8", "fp8"])
@pytest.mark.parametrize("capacity", [None, 20])
def test_paged_decode_reads_one_layer_of_stacked_pools(impl, pool,
                                                       capacity):
    """The paged kernel handed pools stacked over layers [L, P, Hkv, ps, E]
    and a (traced) layer index gives, for every layer, exactly the output
    it gives on that layer's pool alone — with sentinel page ids in the
    table, quantized pools (scales stacked alike) and a ring capacity
    that truncates the logical view."""
    n_layers, b, hq, hkv, e = 3, 2, 4, 2, 16
    n_pages, ps = 10, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, hq, 1, e), jnp.float32)
    shape = (n_layers, n_pages, hkv, ps, e)
    if pool == "bf16":
        k = jax.random.normal(ks[1], shape, jnp.bfloat16)
        v = jax.random.normal(ks[2], shape, jnp.bfloat16)
        k_s = v_s = None
    else:
        qdt = jnp.int8 if pool == "int8" else jnp.float8_e4m3fn
        k, k_s = _quantized_pool(ks[1], shape, qdt)
        v, v_s = _quantized_pool(ks[2], shape, qdt)
    sentinel = n_pages
    bt = jnp.asarray([[3, 9, sentinel, sentinel],
                      [2, 5, 0, sentinel]], jnp.int32)
    kv_len = jnp.asarray([13, 20], jnp.int32)
    if capacity is not None:
        kv_len = jnp.minimum(kv_len, capacity)

    def run(k, v, k_s, v_s, layer):
        return fusemax_decode_paged(q, k, v, bt, kv_len, layer=layer,
                                    capacity=capacity, impl=impl,
                                    k_scale=k_s, v_scale=v_s)

    stacked = jax.jit(run)
    one = jax.jit(lambda k, v, k_s, v_s: run(k, v, k_s, v_s, 0))
    pick = lambda a, l: None if a is None else a[l]
    outs = []
    for l in range(n_layers):
        got = stacked(k, v, k_s, v_s, jnp.int32(l))
        want = one(k[l], v[l], pick(k_s, l), pick(v_s, l))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=f"layer {l}")
        outs.append(np.asarray(got))
    assert not np.array_equal(outs[0], outs[1])     # the layers differ


@pytest.mark.parametrize("tok,shape,window", [
    (1, (2, 6, 3, 4, 5), None),      # GQA pages [L, P, Hkv, ps, dh]
    (1, (2, 6, 3, 4), None),         # GQA scales [L, P, Hkv, ps]
    (0, (2, 6, 4, 5), None),         # MLA latent pages [L, P, ps, r]
    (0, (2, 6, 4, 5), 8),            # ... on a ring of 8 tokens
])
def test_write_page_rows_matches_the_scatter(tok, shape, window):
    """The in-place row writes of the decode step change a stacked pool
    exactly as the page scatter changes that layer's pool: the same
    rows, ring wrap included, and nothing where a row is not valid or its
    table entry is the sentinel page (a clamped write would land on the
    last real page)."""
    from repro.model.attention import (
        write_kv_pages, write_page_rows, write_pages)
    layer = 1
    n_pages, ps = shape[1], shape[2 + tok]
    pool = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.float32)
    bt = jnp.asarray([[4, 1, 5], [2, 6, 6], [0, 3, 6]], jnp.int32)
    # slot 1 sits on the sentinel page 6, slot 2 is not valid
    positions = jnp.asarray([[5, 6], [9, 10], [1, 2]], jnp.int32)
    valid = jnp.asarray([[True, True], [True, True], [False, False]])
    cap = window if window is not None else bt.shape[1] * ps
    new_shape = (3, *shape[2:2 + tok], 2, *shape[3 + tok:])
    values = jax.random.normal(jax.random.PRNGKey(1), new_shape)

    got = jax.jit(lambda pool, lyr: write_page_rows(
        pool, lyr, bt, positions, values, cap, valid, tok=tok))(
            pool, jnp.int32(layer))
    scatter = write_kv_pages if tok == 1 else write_pages
    want = pool.at[layer].set(
        scatter(pool[layer], bt, positions, values, cap, valid))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.array_equal(np.asarray(got), np.asarray(pool))


@pytest.mark.parametrize("arch,unroll", [
    ("gemma2-9b-smoke", False), ("stablelm-1.6b-smoke", False),
    ("stablelm-1.6b-smoke", True), ("hymba-1.5b-smoke", False),
    ("deepseek-v3-671b-smoke", False)])
def test_paged_decode_loop_matches_per_token_steps(arch, unroll):
    """``decode_loop`` over the paged layout (pools carried through the
    layer loop, written in place) gives the same tokens and the same final
    pools and state as per-token ``decode_step`` calls: a local/global
    pattern (gemma2), one pattern position (stablelm, also with the layer
    loop unrolled), runs of one repeat with SSM state beside the pools
    (hymba), and paged MLA (deepseek).  Slot 1 runs out of budget
    mid-loop, slot 2 is free (kv_len 0, sentinel table row)."""
    cfg = get_config(arch)
    rt = dataclasses.replace(RT, unroll_runs=unroll)
    params, _ = tf.init(cfg, jax.random.PRNGKey(0), rt)
    slots, max_len, ps, n_steps = 3, 64, 8, 4
    keys = {"full": max_len}
    keys.update({f"w{s.window}": s.window for s in cfg.layer_specs()
                 if s.window is not None})
    widths = {k: -(-cap // ps) for k, cap in keys.items()}
    num_pages = {k: 2 * w for k, w in widths.items()}
    caches = tf.init_paged_cache(cfg, slots, num_pages, ps, jnp.float32)
    # slots 0 and 1 own interleaved pages; slot 2 holds only the sentinel
    tables = {k: jnp.asarray([list(range(0, 2 * w, 2)),
                              list(range(1, 2 * w, 2)),
                              [num_pages[k]] * w], jnp.int32)
              for k, w in widths.items()}

    lens = [11, 6]
    prompts = np.random.default_rng(0).integers(0, cfg.vocab, (2, 11))
    logits, caches = tf.prefill(
        cfg, params, {"inputs": jnp.asarray(prompts, jnp.int32)}, caches,
        rt, true_len=jnp.asarray(lens, jnp.int32),
        block_tables={k: t[:2] for k, t in tables.items()},
        slot_ids=jnp.asarray([0, 1], jnp.int32))
    logits = jnp.concatenate(
        [logits.astype(jnp.float32), jnp.zeros((1, logits.shape[-1]))])
    kv_len = jnp.asarray(lens + [0], jnp.int32)
    remaining = jnp.asarray([n_steps, 2, 0], jnp.int32)
    key = jax.random.PRNGKey(1)

    loop = jax.jit(lambda c, kv, lg, rem: tf.decode_loop(
        cfg, params, c, kv, lg, rem, key, n_steps=n_steps, rt=rt,
        block_tables=tables))
    toks, loop_caches, loop_kv, _, _, _, steps = loop(
        caches, kv_len, logits, remaining)

    step = jax.jit(lambda c, t, kv: tf.decode_step(
        cfg, params, t, c, kv, rt, block_tables=tables))
    want = []
    for _ in range(n_steps):
        active = remaining > 0
        nxt = jnp.where(active, jnp.argmax(logits, -1), 0).astype(jnp.int32)
        want.append(np.asarray(nxt))
        kv_len = kv_len + active
        new, caches = step(caches, nxt[:, None], kv_len)
        logits = jnp.where(active[:, None], new.astype(jnp.float32), logits)
        remaining = remaining - active
    assert int(steps) == n_steps
    np.testing.assert_array_equal(np.asarray(toks), np.stack(want))
    np.testing.assert_array_equal(np.asarray(loop_kv), np.asarray(kv_len))
    for got, ref in zip(jax.tree.leaves(loop_caches),
                        jax.tree.leaves(caches)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# ---------------------------------------------------------------------------
# property test: ring parity under random geometry (hypothesis-guarded)
# ---------------------------------------------------------------------------

def test_ring_parity_property():
    pytest.importorskip("hypothesis")   # property tests degrade to skips
    from hypothesis import given, settings, strategies as st

    cfg = get_config("gemma2-9b-smoke")
    params, _ = tf.init(cfg, jax.random.PRNGKey(0), RT)
    w = cfg.layer_specs()[0].window
    max_len = 128

    @settings(max_examples=4, deadline=None)
    @given(plen=st.integers(min_value=4, max_value=max_len - 8),
           page_size=st.sampled_from([8, 16, 32]),
           seed=st.integers(min_value=0, max_value=2**31 - 1))
    def check(plen, page_size, seed):
        prompt = np.random.default_rng(seed).integers(
            0, cfg.vocab, plen).astype(np.int32)
        toks = jnp.asarray(prompt)[None]
        dcaches = tf.init_cache(cfg, 1, max_len, jnp.float32)
        dlog, dcaches = tf.prefill(cfg, params, {"inputs": toks}, dcaches,
                                   RT)
        keys = {"full": max_len, f"w{w}": w}
        bts = {k: jnp.asarray(
            [list(range(-(-cap // page_size)))], jnp.int32)
            for k, cap in keys.items()}
        num_pages = {k: -(-cap // page_size) for k, cap in keys.items()}
        pcaches = tf.init_paged_cache(cfg, 1, num_pages, page_size,
                                      jnp.float32)
        plog, pcaches = tf.prefill(
            cfg, params, {"inputs": toks}, pcaches, RT,
            true_len=jnp.asarray([plen], jnp.int32), block_tables=bts,
            slot_ids=jnp.asarray([0], jnp.int32))
        assert bool((dlog == plog).all())
        kv, dl, plg = plen, dlog, plog
        for _ in range(3):
            nd = int(jnp.argmax(dl[0]))
            kv += 1
            dl, dcaches = tf.decode_step(
                cfg, params, jnp.asarray([[nd]]), dcaches,
                jnp.asarray([kv], jnp.int32), RT)
            plg, pcaches = tf.decode_step(
                cfg, params, jnp.asarray([[nd]]), pcaches,
                jnp.asarray([kv], jnp.int32), RT, block_tables=bts)
            assert bool((dl == plg).all())

    check()
