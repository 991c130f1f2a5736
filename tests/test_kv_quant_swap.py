"""Quantized KV pages + host-memory swap tier (PR 8).

Quantized pools store pages in fp8_e4m3 / int8 with per-token fp16
scales in a parallel pool; dequantization happens inside the paged
read paths, so COW, the prefix hash, and swap blobs all see raw
quantized bytes.  The swap tier demotes evicted prefix chains to host
RAM and promotes them back on a later hit (DMA instead of recompute).

Contracts under test:
  * quantize→write→gather→dequantize round-trips within the storage
    dtype's quantization step,
  * greedy streams under quantization stay close to the exact paged
    stream (bounded drift, measured) on GQA and MLA configs,
  * a demote→promote→hit cycle reproduces the never-evicted greedy
    stream exactly (the swap tier is lossless),
  * COW on a quantized shared page leaves the donor's quantized bytes
    AND its scales bitwise untouched,
  * clear_prefix / warmup drain the host tier completely.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.model import transformer as tf
from repro.model.attention import (
    dequantize_kv, gqa_init_paged_cache, kv_quant_dtype, quantize_kv,
)
from repro.model.layers import Runtime
from repro.serving.engine import Request, ServeEngine
from repro.serving.kv_cache import PagedKVCache

RT = Runtime(activation_dtype=jnp.float32, param_dtype=jnp.float32)


def _serve(cfg, params, prompts, layout, new_tokens=4, slots=2,
           max_len=64, **kw):
    eng = ServeEngine(cfg, params, slots=slots, max_len=max_len, rt=RT,
                      decode_chunk=4, cache_layout=layout, **kw)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=new_tokens)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.done for r in reqs)
    return [list(r.generated) for r in reqs], eng


def _match_rate(a_streams, b_streams):
    tot = hit = 0
    for a, b in zip(a_streams, b_streams):
        tot += max(len(a), len(b))
        hit += sum(1 for x, y in zip(a, b) if x == y)
    return hit / max(1, tot)


# ---------------------------------------------------------------------------
# round-trip parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_dtype,step", [("fp8_e4m3", 1 / 8),
                                           ("int8", 0.5 / 127)])
def test_quant_roundtrip_within_dtype_step(kv_dtype, step):
    """quantize→dequantize error per token is bounded by the storage
    grid: half a ULP of e4m3 (relative step 2^-3 at the top binade) /
    half an int8 bucket, measured against the token's own amax."""
    rng = np.random.default_rng(0)
    v = jnp.asarray(rng.normal(size=(5, 16, 32)).astype(np.float32))
    # include edge-case tokens: all-zero, tiny, huge
    v = v.at[0, 0].set(0.0)
    v = v.at[0, 1].set(1e-6 * v[0, 1])
    v = v.at[0, 2].set(1e4 * v[0, 2])
    qdt = kv_quant_dtype(kv_dtype)
    q, s = quantize_kv(v, qdt)
    assert q.dtype == qdt and s.dtype == jnp.float16
    assert s.shape == v.shape[:-1]
    back = dequantize_kv(q, s)
    amax = np.maximum(np.abs(np.asarray(v)).max(-1), 1e-30)
    err = np.abs(np.asarray(back) - np.asarray(v)).max(-1)
    # fp16 scale storage adds ~5e-4 relative on top of the grid step;
    # tokens with amax below ~1e-5 clamp their scale at fp16's smallest
    # subnormal (coarser relative grid, but absolute error stays < 3e-5)
    assert (err <= amax * (step + 1e-3) + 3e-5).all(), (err / amax).max()


def test_quant_page_write_gather_parity():
    """Through the real page machinery: quantize fresh K, scatter data
    and scales into their pools with ``write_kv_pages``, gather through
    a block table, dequantize — matches the direct round-trip bitwise."""
    from repro.kernels.ops import gather_kv_pages
    from repro.model.attention import write_kv_pages

    cfg = get_config("stablelm-1.6b-smoke")
    cache = gqa_init_paged_cache(cfg, num_pages=6, page_size=8,
                                 dtype=jnp.float32, kv_dtype="fp8_e4m3")
    rng = np.random.default_rng(1)
    k_new = jnp.asarray(              # [B=1, Hkv, S=16, dh]
        rng.normal(size=(1, cfg.n_kv_heads, 16, cfg.dh))
        .astype(np.float32))
    q, s = quantize_kv(k_new, cache["k_pages"].dtype)
    bt = jnp.asarray([[2, 4]], jnp.int32)
    pos = jnp.arange(16, dtype=jnp.int32)[None]
    pages = write_kv_pages(cache["k_pages"], bt, pos, q, 64,
                           jnp.asarray([16], jnp.int32))
    scales = write_kv_pages(cache["k_scale"], bt, pos, s, 64,
                            jnp.asarray([16], jnp.int32))
    got = dequantize_kv(gather_kv_pages(pages, bt)[:, :, :16],
                        gather_kv_pages(scales, bt)[:, :, :16])
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(dequantize_kv(q, s)))


# ---------------------------------------------------------------------------
# greedy quality under quantization (bounded, measured)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_dtype", ["fp8_e4m3", "int8"])
def test_quant_greedy_quality_gqa(kv_dtype):
    cfg = get_config("stablelm-1.6b-smoke")
    params, _ = tf.init(cfg, jax.random.PRNGKey(0), RT)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab, l).astype(np.int32)
               for l in (12, 25, 18, 30)]
    exact, _ = _serve(cfg, params, prompts, "paged", new_tokens=6)
    quant, qe = _serve(cfg, params, prompts, "paged", new_tokens=6,
                       kv_dtype=kv_dtype)
    assert qe.kv.kv_dtype == kv_dtype
    qe.kv.check_invariants()    # incl. scale-pool/data-pool page parity
    rate = _match_rate(exact, quant)
    assert rate >= 0.9, (rate, exact, quant)


def test_quant_greedy_quality_mla():
    moe_cfg = get_config("deepseek-v3-671b-smoke")
    cfg = dataclasses.replace(moe_cfg, moe=None)
    params, _ = tf.init(cfg, jax.random.PRNGKey(0), RT)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab, l).astype(np.int32)
               for l in (14, 22, 9)]
    exact, _ = _serve(cfg, params, prompts, "paged", new_tokens=6)
    quant, _ = _serve(cfg, params, prompts, "paged", new_tokens=6,
                      kv_dtype="fp8_e4m3")
    rate = _match_rate(exact, quant)
    assert rate >= 0.9, (rate, exact, quant)


# ---------------------------------------------------------------------------
# COW on a quantized shared page
# ---------------------------------------------------------------------------

def test_cow_on_quantized_shared_page_immutable():
    """A full-page hit on a *quantized* shared page COWs before the tail
    rewrite; the donor page's quantized bytes and its scale rows must
    stay bitwise untouched, and the identical resend must reproduce the
    donor's greedy stream."""
    cfg = get_config("stablelm-1.6b-smoke")
    params, _ = tf.init(cfg, jax.random.PRNGKey(0), RT)
    rng = np.random.default_rng(3)
    p32 = rng.integers(0, cfg.vocab, 32).astype(np.int32)   # 2 full pages
    pdiv = p32.copy()
    pdiv[20] = (pdiv[20] + 1) % cfg.vocab

    eng = ServeEngine(cfg, params, slots=2, max_len=64, rt=RT,
                      decode_chunk=4, cache_layout="paged", page_size=16,
                      prefix_caching=True, kv_dtype="fp8_e4m3")
    first = Request(rid=0, prompt=p32, max_new_tokens=4)
    eng.submit(first)
    eng.run()
    donor = {h: e.page for h, e in eng.kv._prefix.items()}
    assert len(donor) >= 2
    attn = eng.caches[0][0]["attn"]
    assert attn["k_pages"].dtype == kv_quant_dtype("fp8_e4m3")
    snap = {}
    for name in ("k_pages", "v_pages", "k_scale", "v_scale"):
        leaf = np.asarray(attn[name])
        snap[name] = {p: leaf[:, p].copy() for p in donor.values()}

    second = Request(rid=1, prompt=p32, max_new_tokens=4)
    third = Request(rid=2, prompt=pdiv, max_new_tokens=4)
    eng.submit(second)
    eng.submit(third)
    eng.run()
    assert eng.stats["cow_copies"] >= 1
    attn = eng.caches[0][0]["attn"]
    for name, pages in snap.items():
        leaf = np.asarray(attn[name])
        for p, before in pages.items():
            np.testing.assert_array_equal(leaf[:, p], before, err_msg=name)
    assert second.generated == first.generated


# ---------------------------------------------------------------------------
# host swap tier: demote → promote → hit
# ---------------------------------------------------------------------------

def _swap_engine(cfg, params, **kw):
    return ServeEngine(cfg, params, slots=2, max_len=64, rt=RT,
                       decode_chunk=4, cache_layout="paged", page_size=8,
                       prefix_caching=True, **kw)


def _run_one(eng, rid, prompt, new_tokens=4):
    r = Request(rid=rid, prompt=prompt, max_new_tokens=new_tokens)
    eng.submit(r)
    eng.run()
    assert r.done
    return list(r.generated)


def test_demote_promote_hit_greedy_equivalence():
    """Fill a tiny pool so the next admission evicts A's prefix chain
    (demoting it to host RAM), then resend A: the chain promotes back
    via DMA, the admission counts as a prefix hit, and the greedy stream
    matches a never-evicted engine exactly."""
    cfg = get_config("stablelm-1.6b-smoke")
    params, _ = tf.init(cfg, jax.random.PRNGKey(0), RT)
    rng = np.random.default_rng(5)
    pa = rng.integers(0, cfg.vocab, 24).astype(np.int32)   # 3 pages
    pb = rng.integers(0, cfg.vocab, 40).astype(np.int32)   # 5+ pages

    # reference: pool big enough that nothing is ever evicted
    ref = _swap_engine(cfg, params, num_pages=64)
    ra1 = _run_one(ref, 0, pa)
    _run_one(ref, 1, pb)
    ra2 = _run_one(ref, 2, pa)
    assert ref.kv.stats["demotions"] == 0

    # 9-page pool: serving B (6 pages incl. decode growth) must evict
    # A's indexed chain — with the swap tier on, that's a demotion
    eng = _swap_engine(cfg, params, num_pages=8,
                       host_swap_bytes=1 << 30)
    assert eng.kv.swap_enabled
    a1 = _run_one(eng, 0, pa)
    assert eng.kv.match_prefix(pa) >= 3
    _run_one(eng, 1, pb)
    st = eng.kv.stats
    assert st["demotions"] >= 3, st
    demoted = [e for e in eng.kv._prefix.values() if e.page < 0]
    assert demoted and all(e.host is not None for e in demoted)

    eng.kv.check_invariants()   # demoted entries hold blobs, bytes match
    a2 = _run_one(eng, 2, pa)
    st = eng.kv.stats
    assert st["promotions"] >= 3, st
    eng.kv.check_invariants()
    assert eng.stats["prefix_hits"] >= 1
    # 24-token resend over a 3-full-page hit: the exact-cover COW
    # re-prefills the final token, so 23 of 24 prompt tokens are reused
    assert eng.stats["tokens_reused"] >= 23
    assert (a1, a2) == (ra1, ra2)

    ht = eng.memory_stats()["host_tier"]
    assert ht["enabled"] and ht["demotions"] == st["demotions"]
    assert ht["promote_hit_rate"] > 0


def test_host_tier_byte_cap_drops_lru():
    """A swap budget smaller than one demoted chain can hold must drop
    LRU demoted chains (HBM → host → drop ordering) instead of growing
    without bound."""
    cfg = get_config("stablelm-1.6b-smoke")
    params, _ = tf.init(cfg, jax.random.PRNGKey(0), RT)
    bpp = None
    rng = np.random.default_rng(6)
    # budget of exactly 2 pages: demoting a 3-page chain must make room
    # by dropping earlier demoted pages
    probe = ServeEngine(cfg, params, slots=2, max_len=64, rt=RT,
                        cache_layout="paged", page_size=8)
    bpp = probe.kv.classes["full"].bytes_per_page
    eng = _swap_engine(cfg, params, num_pages=8,
                       host_swap_bytes=2 * bpp)
    pa = rng.integers(0, cfg.vocab, 24).astype(np.int32)
    pb = rng.integers(0, cfg.vocab, 40).astype(np.int32)
    _run_one(eng, 0, pa)
    _run_one(eng, 1, pb)
    st = eng.kv.stats
    # the 3-page chain exceeds the 2-page budget → dropped, not demoted
    assert st["demotions"] == 0 and st["host_drops"] == 0
    assert eng.kv._host_bytes <= 2 * bpp
    assert eng.kv.stats["prefix_evictions"] > 0


def test_swap_host_tier_drains():
    """clear_prefix (and therefore warmup) must leave zero demoted pages
    and zero host bytes — warmup traffic must not strand blobs in the
    host tier."""
    cfg = get_config("stablelm-1.6b-smoke")
    params, _ = tf.init(cfg, jax.random.PRNGKey(0), RT)
    rng = np.random.default_rng(7)
    eng = _swap_engine(cfg, params, num_pages=8,
                       host_swap_bytes=1 << 30)
    _run_one(eng, 0, rng.integers(0, cfg.vocab, 24).astype(np.int32))
    _run_one(eng, 1, rng.integers(0, cfg.vocab, 40).astype(np.int32))
    assert eng.kv.stats["demotions"] > 0
    ht = eng.memory_stats()["host_tier"]
    assert ht["demoted_pages"] > 0 and ht["demoted_bytes"] > 0

    eng.clear_prefix_cache()
    ht = eng.memory_stats()["host_tier"]
    assert ht["demoted_pages"] == 0 and ht["demoted_bytes"] == 0
    assert eng.kv._host_bytes == 0
    assert all(v == 0 for v in eng.kv.pages_in_use.values())
    eng.kv.check_invariants()

    # warmup ends with clear_prefix: no demoted residue either
    eng.warmup([24, 40])
    ht = eng.memory_stats()["host_tier"]
    assert ht["demoted_pages"] == 0 and ht["demoted_bytes"] == 0
    assert eng.kv._host_bytes == 0


def test_swap_compounds_with_quantized_pages():
    """The full capacity stack: quantized pages demote and promote as
    raw bytes — a post-swap hit still reproduces the no-swap quantized
    stream (swap is lossless even when the payload is lossy-encoded)."""
    cfg = get_config("stablelm-1.6b-smoke")
    params, _ = tf.init(cfg, jax.random.PRNGKey(0), RT)
    rng = np.random.default_rng(8)
    pa = rng.integers(0, cfg.vocab, 24).astype(np.int32)
    pb = rng.integers(0, cfg.vocab, 40).astype(np.int32)

    ref = _swap_engine(cfg, params, num_pages=64, kv_dtype="fp8_e4m3")
    streams_ref = [_run_one(ref, i, p) for i, p in
                   enumerate((pa, pb, pa))]
    eng = _swap_engine(cfg, params, num_pages=8, kv_dtype="fp8_e4m3",
                       host_swap_bytes=1 << 30)
    streams = [_run_one(eng, i, p) for i, p in enumerate((pa, pb, pa))]
    assert eng.kv.stats["promotions"] > 0
    assert streams == streams_ref
