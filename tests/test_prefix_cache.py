"""Automatic prefix caching on the paged KV cache: shared-prefix
admission is greedy-bit-identical to cold prefill (dense vs paged vs
paged+prefix), COW isolates divergent continuations from the shared
pages, refcounts never go negative and the pool drains once the index is
dropped, and double frees raise."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.model import transformer as tf
from repro.model.layers import Runtime
from repro.serving.engine import Request, ServeEngine
from repro.serving.kv_cache import PagePool, PagedKVCache

RT = Runtime(activation_dtype=jnp.float32, param_dtype=jnp.float32)


def _serve(cfg, params, prompts, layout, new_tokens=5, **kw):
    eng = ServeEngine(cfg, params, slots=2, max_len=64, rt=RT,
                      decode_chunk=4, cache_layout=layout, **kw)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=new_tokens)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.done for r in reqs)
    return [list(r.generated) for r in reqs], eng


# ---------------------------------------------------------------------------
# greedy equivalence: dense vs paged vs paged + prefix cache
# ---------------------------------------------------------------------------

def test_shared_prefix_matches_cold_prefill():
    """The acceptance property: shared-system-prompt traffic through the
    prefix cache emits the same greedy tokens as cold prefill on every
    layout, while reusing the shared head pages instead of re-prefilling
    them."""
    cfg = get_config("stablelm-1.6b-smoke")
    params, _ = tf.init(cfg, jax.random.PRNGKey(0), RT)
    rng = np.random.default_rng(0)
    shared = rng.integers(0, cfg.vocab, 32).astype(np.int32)  # 2 pages
    prompts = [np.concatenate(
        [shared, rng.integers(0, cfg.vocab, t).astype(np.int32)])
        for t in (8, 5, 11, 8, 3, 9)]

    dense, _ = _serve(cfg, params, prompts, "dense")
    cold, _ = _serve(cfg, params, prompts, "paged", page_size=16,
                     prefix_caching=False)
    warm, pe = _serve(cfg, params, prompts, "paged", page_size=16,
                      prefix_caching=True)
    assert dense == cold == warm
    pe.kv.check_invariants()
    # first request is the cold writer; every later one maps the 2 shared
    # pages (admission-time registration shares across live slots too)
    assert pe.stats["prefix_hits"] == len(prompts) - 1
    assert pe.stats["tokens_reused"] == (len(prompts) - 1) * 32
    # prefill dispatch work drops by exactly the reused tokens
    total = sum(len(p) for p in prompts)
    assert pe.stats["tokens_prefilled"] == total - pe.stats["tokens_reused"]


def test_prefix_disabled_for_windowed_and_ssm_configs():
    """Ring working sets and SSM running state are not reconstructible
    from retained pages — the feature must gate itself off, not corrupt."""
    g2 = get_config("gemma2-9b-smoke")
    kv = PagedKVCache(g2, slots=2, max_len=128, dtype=jnp.float32,
                      page_size=16, prefix_caching=True)
    assert not kv.prefix_supported and not kv.prefix_enabled
    info = kv.admit(0, np.arange(20, dtype=np.int32), 21)
    assert info == {"cached_len": 0, "reused": 0, "cow_pairs": [],
                    "promotes": []}
    kv.release(0, tokens=np.arange(20, dtype=np.int32))
    assert all(v == 0 for v in kv.pages_in_use.values())


# ---------------------------------------------------------------------------
# copy-on-write isolation
# ---------------------------------------------------------------------------

def test_cow_isolation_on_divergence():
    """A prompt that exactly covers its prefix-cache hit re-prefills its
    last token into a COW copy; the index-held page must stay bitwise
    untouched so other hits keep reading the original content."""
    cfg = get_config("stablelm-1.6b-smoke")
    params, _ = tf.init(cfg, jax.random.PRNGKey(0), RT)
    rng = np.random.default_rng(3)
    p32 = rng.integers(0, cfg.vocab, 32).astype(np.int32)   # 2 full pages
    pdiv = p32.copy()
    pdiv[20] = (pdiv[20] + 1) % cfg.vocab                   # diverges in page 1

    eng = ServeEngine(cfg, params, slots=2, max_len=64, rt=RT,
                      decode_chunk=4, cache_layout="paged", page_size=16,
                      prefix_caching=True)
    first = Request(rid=0, prompt=p32, max_new_tokens=4)
    eng.submit(first)
    eng.run()
    donor = {h: e.page for h, e in eng.kv._prefix.items()}
    assert len(donor) >= 2                  # both prompt pages indexed
    # stacked-run leaf: [reps, P, Hkv, page_size, dh] — page axis is 1
    leaf = np.asarray(eng.caches[0][0]["attn"]["k_pages"])
    snap = {p: leaf[:, p].copy() for p in donor.values()}

    # identical prompt (full-page hit → COW) and a divergent one together
    second = Request(rid=1, prompt=p32, max_new_tokens=4)
    third = Request(rid=2, prompt=pdiv, max_new_tokens=4)
    eng.submit(second)
    eng.submit(third)
    eng.run()
    assert eng.stats["cow_copies"] >= 1
    leaf = np.asarray(eng.caches[0][0]["attn"]["k_pages"])
    for p, before in snap.items():
        np.testing.assert_array_equal(leaf[:, p], before)
    # greedy streams: identical prompt reproduces the donor's stream;
    # everything matches the dense reference
    dense, _ = _serve(cfg, params, [p32, p32, pdiv], "dense",
                      new_tokens=4)
    assert [first.generated, second.generated, third.generated] == dense


# ---------------------------------------------------------------------------
# refcounts, double free, sentinel, drain
# ---------------------------------------------------------------------------

def test_page_pool_refcounts_and_double_free():
    pool = PagePool(4)
    (a, b) = pool.alloc(2)
    pool.ref(a)                               # shared: rc=2
    assert pool.refcount(a) == 2
    with pytest.raises(RuntimeError):
        pool.free([a])                        # freeing a shared page
    assert not pool.unref(a)                  # rc back to 1, not freed
    assert pool.unref(a)                      # rc 0 → freed
    with pytest.raises(RuntimeError):
        pool.free([a])                        # double free raises
    with pytest.raises(RuntimeError):
        pool.unref(a)                         # refcount never negative
    pool.free([b])
    with pytest.raises(RuntimeError):
        pool.free([b])                        # double free while others live
    assert pool.pages_in_use == 0 and pool.free_pages == 4
    with pytest.raises(RuntimeError):
        pool.ref(3)                           # ref of unallocated page


def test_sentinel_rows_never_live():
    cfg = get_config("stablelm-1.6b-smoke")
    kv = PagedKVCache(cfg, slots=2, max_len=64, dtype=jnp.float32,
                      page_size=16, num_pages=6)
    sentinel = kv.classes["full"].pool.num_pages
    assert (kv.classes["full"].table == sentinel).all()   # fresh = unbacked
    assert kv.grow(0, 20)
    tbl = kv.classes["full"].table
    assert (tbl[0, :2] < sentinel).all()      # live rows hold real pages
    assert (tbl[0, 2:] == sentinel).all() and (tbl[1] == sentinel).all()
    kv.tables()                               # invariant holds
    kv.classes["full"].table[0, 0] = sentinel  # simulate a table slip
    with pytest.raises(AssertionError):
        kv.tables()
    kv.classes["full"].table[0, 0] = kv.classes["full"].owned[0][0]
    kv.release(0)
    assert (kv.classes["full"].table == sentinel).all()


def test_pool_drains_to_full_on_idle():
    """After the trace completes, live residency is zero, the retained
    pages are exactly the prefix index's, every refcount is positive, and
    dropping the index drains the pool completely."""
    cfg = get_config("stablelm-1.6b-smoke")
    params, _ = tf.init(cfg, jax.random.PRNGKey(0), RT)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, l).astype(np.int32)
               for l in (20, 33, 17)]
    _, eng = _serve(cfg, params, prompts, "paged", page_size=16,
                    prefix_caching=True)
    kv = eng.kv
    pool = kv.classes["full"].pool
    m = eng.memory_stats()
    assert m["resident_cache_bytes"] == 0
    assert m["prefix_cache"]["entries"] == pool.pages_in_use > 0
    assert all(pool.refcount(e.page) == 1 for e in kv._prefix.values())
    kv.check_invariants()
    dropped = eng.clear_prefix_cache()
    assert dropped == m["prefix_cache"]["entries"]
    assert pool.pages_in_use == 0 and pool.free_pages == pool.num_pages
    assert len(kv._prefix) == 0
    kv.check_invariants()


def test_admit_never_evicts_its_own_match():
    """Under pool pressure, admission must not evict the very chain it
    just matched (the entries are not ref'd until after eviction runs) —
    it backs off instead of crashing or serving freed pages."""
    cfg = get_config("stablelm-1.6b-smoke")
    kv = PagedKVCache(cfg, slots=2, max_len=64, dtype=jnp.float32,
                      page_size=16, num_pages=6)
    rng = np.random.default_rng(13)
    a = rng.integers(0, cfg.vocab, 40).astype(np.int32)    # 2 full pages
    b = rng.integers(0, cfg.vocab, 40).astype(np.int32)
    info = kv.admit(0, a, 41)
    assert info is not None and info["cached_len"] == 0
    kv.release(0, tokens=a)                     # index ← a's 2 full pages
    assert kv.match_prefix(a) == 2
    assert kv.admit(1, b, 41) is not None       # different prompt: 3 fresh
    # pool: 2 index-held (a) + 3 slot-1 pages = 5 in use, 1 free; b's
    # admission also indexed its own 2 prompt pages (refcount 2 — not
    # evictable).  Extending `a` matches a's 2 index pages and needs 2
    # fresh — the only evictable pages ARE the matched ones, so admission
    # must refuse with state unchanged rather than evict its own match.
    c = np.concatenate([a, rng.integers(0, cfg.vocab, 13).astype(np.int32)])
    pool = kv.classes["full"].pool
    entries_before = len(kv._prefix)
    free_before = pool.free_pages
    assert kv.admit(0, c, len(c) + 1) is None
    assert kv.match_prefix(a) == 2              # matched chain survived
    assert len(kv._prefix) == entries_before
    assert pool.free_pages == free_before
    assert kv.classes["full"].owned[0] == []
    kv.check_invariants()


def test_prefix_eviction_under_pool_pressure():
    """A pool too small to retain every completed prefix must evict LRU
    index entries to admit new work — and still match dense greedy."""
    cfg = get_config("stablelm-1.6b-smoke")
    params, _ = tf.init(cfg, jax.random.PRNGKey(0), RT)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab, l).astype(np.int32)
               for l in (18, 25, 21, 30)]
    dense, _ = _serve(cfg, params, prompts, "dense", new_tokens=4)
    paged, pe = _serve(cfg, params, prompts, "paged", new_tokens=4,
                       page_size=8, num_pages=8, prefix_caching=True)
    assert dense == paged
    assert pe.kv.stats["prefix_evictions"] > 0
    pe.kv.check_invariants()
    pe.clear_prefix_cache()
    assert all(v == 0 for v in pe.kv.pages_in_use.values())


def test_page_aligned_stream_end_not_demoted():
    """The fused decode loop keeps issuing masked steps for a slot whose
    budget is spent while others decode — those steps rewrite the
    stream's final position with the dummy token's K/V.  When the stream
    is exactly page-aligned that position sits in the last *full* page,
    so release must not demote it into the index; a prompt extending the
    stream must still match dense greedy."""
    cfg = get_config("stablelm-1.6b-smoke")
    params, _ = tf.init(cfg, jax.random.PRNGKey(0), RT)
    rng = np.random.default_rng(11)
    pa = rng.integers(0, cfg.vocab, 6).astype(np.int32)
    pb = rng.integers(0, cfg.vocab, 6).astype(np.int32)
    tail = rng.integers(0, cfg.vocab, 4).astype(np.int32)

    def serve(layout, **kw):
        eng = ServeEngine(cfg, params, slots=2, max_len=64, rt=RT,
                          decode_chunk=8, cache_layout=layout, **kw)
        # A's stream is 6 + 2 = 8 tokens — exactly one page_size=8 page —
        # and freezes mid-chunk while B keeps decoding (clobbering A's
        # position 7 with masked writes)
        ra = Request(rid=0, prompt=pa, max_new_tokens=2)
        rb = Request(rid=1, prompt=pb, max_new_tokens=10)
        eng.submit(ra)
        eng.submit(rb)
        eng.run()
        # C extends A's completed stream: a hit on A's final page would
        # read the clobbered K/V
        pc = np.concatenate(
            [pa, np.asarray(ra.generated, np.int32), tail])
        # A's one-and-only full page covers its stream end → it must not
        # have been demoted at completion (admission registered nothing
        # either: the 6-token prompt has no full page), so C cannot hit
        # the clobbered page
        if eng.kv is not None:
            assert eng.kv.match_prefix(pc) == 0
        rc = Request(rid=2, prompt=pc, max_new_tokens=4)
        eng.submit(rc)
        eng.run()
        return [list(r.generated) for r in (ra, rb, rc)], eng

    dense, _ = serve("dense")
    paged, pe = serve("paged", page_size=8, prefix_caching=True)
    assert dense == paged
    assert pe.stats["prefix_hits"] == 0


def test_shared_prefix_mla_latents():
    """MLA latents page (and prefix-share) the same way.  deepseek-smoke
    itself gates off (MoE expert capacity depends on the prefilled chunk
    length, so tail-only prefill would re-route tokens), so the paged
    MLA prefix path is exercised on its MoE-free variant."""
    import dataclasses

    moe_cfg = get_config("deepseek-v3-671b-smoke")
    assert not PagedKVCache(moe_cfg, slots=1, max_len=64,
                            dtype=jnp.float32).prefix_supported
    cfg = dataclasses.replace(moe_cfg, moe=None)
    params, _ = tf.init(cfg, jax.random.PRNGKey(0), RT)
    rng = np.random.default_rng(5)
    shared = rng.integers(0, cfg.vocab, 16).astype(np.int32)  # 1 page
    prompts = [np.concatenate(
        [shared, rng.integers(0, cfg.vocab, t).astype(np.int32)])
        for t in (6, 9, 4)]
    dense, _ = _serve(cfg, params, prompts, "dense", new_tokens=4)
    warm, pe = _serve(cfg, params, prompts, "paged", new_tokens=4,
                      page_size=16, prefix_caching=True)
    assert dense == warm
    assert pe.kv.prefix_enabled
    assert pe.stats["tokens_reused"] == (len(prompts) - 1) * 16


def test_paged_decode_sentinel_rows_safe():
    """An inactive slot whose table rows hold the out-of-range sentinel
    must not perturb other slots, on the jnp path and the Pallas kernel
    (reads clamp in the index_map, scores are masked by kv_len)."""
    from repro.kernels import (
        decode_reference, fusemax_decode_paged, gather_kv_pages,
    )
    b, hq, hkv, e, f = 2, 4, 2, 16, 16
    n_pages, ps, width = 10, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (b, hq, 1, e), jnp.float32)
    k_pages = jax.random.normal(ks[1], (n_pages, hkv, ps, e), jnp.float32)
    v_pages = jax.random.normal(ks[2], (n_pages, hkv, ps, f), jnp.float32)
    sentinel = n_pages
    bt = jnp.asarray([[3, 1, 7, sentinel],
                      [sentinel] * width], jnp.int32)      # slot 1 released
    kv_len = jnp.asarray([21, 0], jnp.int32)
    k = gather_kv_pages(k_pages, bt[:1])
    v = gather_kv_pages(v_pages, bt[:1])
    ref = decode_reference(q[:1], k, v, kv_len[:1])
    for impl in ("jnp", "pallas"):
        out = fusemax_decode_paged(q, k_pages, v_pages, bt, kv_len,
                                   impl=impl)
        np.testing.assert_allclose(np.asarray(out[:1]), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5,
                                   err_msg=f"impl={impl}")
        assert np.all(np.isfinite(np.asarray(out)))

