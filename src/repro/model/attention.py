"""Attention layers: GQA (+ sliding window / softcap) and MLA (DeepSeek).

Every attention layer runs on the FuseMax execution engine
(:mod:`repro.kernels.ops`): 1-pass cascade, deferred division — selectable
``impl`` (pallas / jnp / ref) via :class:`repro.model.layers.Runtime`.

Cache protocol (serving):

Dense layout (one row per batch slot, ``max_len`` reserved up front):
  GQA full cache  {"k","v": [B, Hkv, Mmax, dh]}            — global layers
  GQA ring cache  {"k","v": [B, Hkv, window, dh]}          — local layers,
      slot = position % window; RoPE is applied at *write* time with the
      absolute position, so reads need no rotation and the in-window mask
      is implied by the ring (valid = min(t+1, window) slots).
  MLA latent cache {"ckv": [B, Mmax, r], "krope": [B, Mmax, rd]} — decode
      uses the absorbed form (scores in latent space; Hkv=1, group=H).

Paged layout (page pool + per-slot block table indirection — resident
memory tracks live tokens, see :mod:`repro.serving.kv_cache`):
  GQA  {"k_pages","v_pages": [P, Hkv, page_size, dh]}   (head-major)
  MLA  {"ckv_pages": [P, page_size, r], "krope_pages": [P, page_size, rd]}
  Token at logical index l = position % capacity lives at
  (block_table[slot, l // page_size], l % page_size); ``capacity`` is
  ``window`` for local layers (the ring *is* the eviction policy: a
  windowed layer cycles through a fixed ceil(window/page_size)-page
  working set no matter how long the sequence runs) and ``max_len`` for
  global/MLA layers.  Logical index == gathered index, so paged reads are
  bit-identical to the dense layout's.  Prefill scatters into one layer's
  pool; decode and verify take the pools of a whole run, stacked
  ``[L, ...]``, plus the layer index, and write that layer's new rows in
  place (:func:`write_page_rows`) before the kernel reads them there.

Length-bucketed prefill: the ``true_len`` argument on the prefill entry
points marks each row's real prompt length inside a padded (power-of-two
bucketed) batch.  Writes beyond a row's true length are masked (dropped
for paged caches, OOB-slot-dropped for dense rings); full dense caches
tolerate the garbage (masked at read, overwritten by decode).

Device-sharded pools (``rt.kv_shard``, a
:class:`repro.distributed.sharding.KVShard`): page arrays are partitioned
along the kv-head axis (GQA) / latent-rank axis (MLA) over one mesh axis,
with the *page dimension complete on every device* — block tables and
page ids are global, so the host-side allocator is oblivious to the
sharding.  The paged read/write + attention paths then run under
``shard_map``: each device writes and attends only its own head (rank)
slice of the pool, and attention outputs are all-gathered back to the
full head axis *inside* the mapped region so every downstream op (the
output projection in particular, whose head contraction would otherwise
become an order-sensitive cross-device psum) runs replicated on
identically-ordered operands — greedy token streams stay bit-identical
to the single-device paged path.  GQA shards decode compute
head-parallel.  MLA cannot (every absorbed score contracts the full
rank), so its decode shards *split-K-parallel* instead: the sweep is
fixed at one split per block-table page, each device computes the
(RM, RD, RNV) partials for its contiguous 1/tp strip of pages, the
page-ordered partial stacks are all-gathered, and the associative
running-max combine runs replicated — per-device decode FLOPs are 1/tp
and the result is bit-identical to the unsharded per-page sweep.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.kernels.ops import (
    fusemax_attention, fusemax_decode, fusemax_decode_paged,
    fusemax_mla_decode_paged, gather_kv_pages, gather_pages,
    mla_combine_partials, mla_decode_partials,
)
from repro.model.layers import (
    Runtime, _init, apply_norm, norm_init, rope,
)


def paged_cache_key(spec: LayerSpec) -> str:
    """Block-table key for a layer: windowed layers share a table per
    window size; global (and MLA) layers share the "full" table."""
    return "full" if spec.window is None else f"w{spec.window}"


def write_pages(pages: jnp.ndarray, bt_rows: jnp.ndarray,
                positions: jnp.ndarray, values: jnp.ndarray,
                capacity: int, valid: Optional[jnp.ndarray] = None
                ) -> jnp.ndarray:
    """Scatter per-token values into a page pool through block-table rows.

    pages: [P, page_size, *tail]; bt_rows: [N, W]; positions: [N, S]
    absolute token positions; values: [N, S, *tail].  The logical index
    wraps at ``capacity`` (ring eviction for windowed layers).  Rows of
    ``valid`` (same shape as positions) that are False are dropped — their
    page index is pushed out of bounds and jax's scatter ``mode="drop"``
    discards them, so padded bucket tails and unallocated sentinel entries
    never corrupt live pages.
    """
    page, slot = _page_slots(pages.shape[0], pages.shape[1], bt_rows,
                             positions, capacity, valid)
    return pages.at[page, slot].set(values.astype(pages.dtype), mode="drop")


def write_kv_pages(pages: jnp.ndarray, bt_rows: jnp.ndarray,
                   positions: jnp.ndarray, values: jnp.ndarray,
                   capacity: int, valid: Optional[jnp.ndarray] = None
                   ) -> jnp.ndarray:
    """:func:`write_pages` for a head-major GQA pool: pages
    [P, Hkv, page_size, *tail], values [N, Hkv, S, *tail] (the layout the
    projections produce), same wrap and drop rules."""
    page, slot = _page_slots(pages.shape[0], pages.shape[2], bt_rows,
                             positions, capacity, valid)
    heads = jnp.arange(pages.shape[1])[None, :, None]
    return pages.at[page[:, None], heads, slot[:, None]].set(
        values.astype(pages.dtype), mode="drop")


def write_page_rows(pool: jnp.ndarray, layer, bt_rows: jnp.ndarray,
                    positions: jnp.ndarray, values: jnp.ndarray,
                    capacity: int, valid: jnp.ndarray,
                    tok: int) -> jnp.ndarray:
    """Write per-token rows into layer ``layer`` of a page pool stacked
    over layers, in place: the decode-time counterpart of
    :func:`write_pages` / :func:`write_kv_pages` for the pools a layer
    loop carries.

    pool: [L, P, *page], where axis ``tok`` of ``page`` is the in-page
    token slot (GQA pages [Hkv, ps, dh] and scales [Hkv, ps]: 1; MLA
    pages [ps, r] and scales [ps]: 0); values: [N, *page] with the S new
    tokens on that axis; positions, valid: [N, S].  Same wrap rule as
    :func:`write_pages`.

    Each token is one ``dynamic_update_slice`` of a one-token row, which
    XLA performs in place on the carried buffer and in the layout the
    paged kernel reads (a scatter would get a layout of its own, and a
    whole-pool copy into the kernel's layout per layer).  Such a start is
    clamped, not dropped, so a row that is not ``valid``, or whose table
    entry is out of range (the sentinel id ``P``), writes back the row it
    reads and never lands on a real page."""
    n_pages = pool.shape[1]
    page, slot = _page_slots(n_pages, pool.shape[2 + tok], bt_rows,
                             positions, capacity, None)
    ok = valid & (page >= 0) & (page < n_pages)
    lead = (0,) * tok
    tail = (0,) * (pool.ndim - 3 - tok)
    for n in range(values.shape[0]):
        for t in range(values.shape[1 + tok]):
            row = jax.lax.slice_in_dim(values[n], t, t + 1, axis=tok)
            row = row[None, None].astype(pool.dtype)
            start = (layer, page[n, t], *lead, slot[n, t], *tail)
            old = jax.lax.dynamic_slice(pool, start, row.shape)
            pool = jax.lax.dynamic_update_slice(
                pool, jnp.where(ok[n, t], row, old), start)
    return pool


def _write_rows(pools: dict, new: dict, layer, bt_rows, positions,
                capacity: int, valid, tok: int) -> dict:
    """:func:`write_page_rows` of each pool named in ``new`` (name → the
    rows to write) → the updated pools."""
    return {k: write_page_rows(pools[k], layer, bt_rows, positions, v,
                               capacity, valid, tok)
            for k, v in new.items()}


def _page_slots(n_pages: int, page_size: int, bt_rows, positions,
                capacity: int, valid):
    """(page id, in-page slot) per token; ``valid``-False tokens get the
    out-of-bounds page id ``n_pages`` so scatters drop them."""
    l = positions % capacity
    page = jnp.take_along_axis(bt_rows, l // page_size, axis=1)
    if valid is not None:
        page = jnp.where(valid, page, n_pages)           # OOB → dropped
    return page, l % page_size


# ---------------------------------------------------------------------------
# KV-page quantization
#
# Quantized pools store pages in a narrow dtype (fp8_e4m3 / int8) plus a
# parallel fp16 *scale pool* shaped like the data pages minus the trailing
# feature axis — per-token-per-head for GQA ([P, Hkv, ps]), per-token for
# MLA latents ([P, ps]).  Scales store as fp16 (values quantize against
# the *rounded* scale, so the round-trip is still exact on representable
# values; fp16's ~5e-4 relative scale error is dwarfed by fp8's ~4%
# quantization noise) — at head_dim 32 fp32 scales alone would cost 12.5%
# of the bf16 footprint.  Quantization is symmetric per token over the
# feature axis (amax / qmax); dequantization happens at the read site
# (inside the paged kernels / against the gathered table view), never in
# storage — COW copies, swap blobs, and the prefix hash all see raw
# quantized bytes, so the host-side paging machinery is unchanged.
# ---------------------------------------------------------------------------

def kv_quant_dtype(kv_dtype: Optional[str]):
    """Resolve a ``kv_dtype`` name to a jnp storage dtype (None → None)."""
    if kv_dtype is None:
        return None
    if kv_dtype == "fp8_e4m3":
        dt = getattr(jnp, "float8_e4m3fn", None)
        if dt is None:
            raise ValueError(
                "kv_dtype='fp8_e4m3' needs a jax build with float8_e4m3fn")
        return jnp.dtype(dt)
    if kv_dtype == "int8":
        return jnp.dtype(jnp.int8)
    raise ValueError(f"unknown kv_dtype {kv_dtype!r} (fp8_e4m3 | int8)")


def _kv_qmax(qdtype) -> float:
    """Largest representable magnitude of the storage dtype (the
    quantization grid endpoint): 448 for fp8 e4m3, 127 for int8."""
    return 127.0 if jnp.dtype(qdtype) == jnp.dtype(jnp.int8) else 448.0


def quantize_kv(values: jnp.ndarray, qdtype):
    """Symmetric per-token quantization over the trailing feature axis.

    values: [..., feat] → (q [..., feat] in ``qdtype``, scale [...] fp16)
    with ``scale = amax / qmax`` (all-zero tokens get scale 1 so the
    round-trip stays exact).  The scale is rounded to its fp16 storage
    precision *before* quantizing, so q · stored-scale reproduces
    representable values exactly; a floor at the smallest fp16 subnormal
    keeps near-zero tokens from dividing by zero.  int8 rounds to
    nearest; fp8 relies on the cast's native rounding.
    """
    v32 = values.astype(jnp.float32)
    qmax = _kv_qmax(qdtype)
    amax = jnp.max(jnp.abs(v32), axis=-1)
    scale = jnp.where(amax > 0.0, amax / qmax, 1.0).astype(jnp.float16)
    scale = jnp.maximum(scale, jnp.finfo(jnp.float16).smallest_subnormal)
    q = v32 / scale.astype(jnp.float32)[..., None]
    if jnp.dtype(qdtype) == jnp.dtype(jnp.int8):
        q = jnp.round(q)
    return jnp.clip(q, -qmax, qmax).astype(qdtype), scale


def dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray,
                  dtype=jnp.float32) -> jnp.ndarray:
    """Inverse of :func:`quantize_kv`: q [..., feat] × scale [...]."""
    return (q.astype(jnp.float32) *
            scale.astype(jnp.float32)[..., None]).astype(dtype)


def ring_write_masked(kc: jnp.ndarray, vc: jnp.ndarray,
                      k_new: jnp.ndarray, v_new: jnp.ndarray,
                      off: int, true_len: jnp.ndarray
                      ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Write a prompt chunk's K/V ([B, Hkv, S, dh], absolute positions
    [off, off+S)) into a dense ring cache under length-bucket padding:
    per row, keep only positions that are (a) real (< true_len) and
    (b) not already evicted by this chunk's own tail — at most ``window``
    survivors, so ring slots stay collision-free; masked writes drop via
    an out-of-bounds slot index.  Shared by whole-prompt and chunked
    prefill (the single source of the valid-band invariant)."""
    b, _, s_len, _ = k_new.shape
    slots = kc.shape[2]
    tl = true_len[:, None]
    pos = (off + jnp.arange(s_len))[None]                # [1, S] absolute
    valid = (pos < tl) & (pos >= jnp.minimum(tl, off + s_len) - slots)
    slot_idx = jnp.where(valid, pos % slots, slots)      # OOB → dropped
    bidx = jnp.arange(b)[:, None]
    kc = kc.at[bidx, :, slot_idx].set(
        jnp.moveaxis(k_new, 1, 2), mode="drop")
    vc = vc.at[bidx, :, slot_idx].set(
        jnp.moveaxis(v_new, 1, 2), mode="drop")
    return kc, vc


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def gqa_init(key, cfg: ModelConfig, dtype=jnp.float32):
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.dh
    ks = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    so = 1.0 / math.sqrt(h * dh)
    params = {
        "wq": _init(ks[0], (d, h, dh), s, dtype),
        "wk": _init(ks[1], (d, hkv, dh), s, dtype),
        "wv": _init(ks[2], (d, hkv, dh), s, dtype),
        "wo": _init(ks[3], (h, dh, d), so, dtype),
    }
    axes = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    return params, axes


def _proj_qkv(p, x, cfg: ModelConfig, positions, rt: Runtime):
    dt = x.dtype
    q = jnp.einsum("bsd,dhe->bhse", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhe->bhse", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhe->bhse", x, p["wv"].astype(dt))
    q = rope(q, positions[:, None, :], cfg.rope_theta)
    k = rope(k, positions[:, None, :], cfg.rope_theta)
    q = rt.shard_activation(q, ("batch", "heads", "seq", "head_dim"))
    k = rt.shard_activation(k, ("batch", "kv_heads", "seq", "head_dim"))
    v = rt.shard_activation(v, ("batch", "kv_heads", "seq", "head_dim"))
    return q, k, v


def gqa_forward(
    p, x: jnp.ndarray, cfg: ModelConfig, spec: LayerSpec, rt: Runtime,
    positions: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Full-sequence (training / prefill) attention. x: [B, S, d]."""
    b, s_len, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s_len), (b, s_len))
    q, k, v = _proj_qkv(p, x, cfg, positions, rt)
    out = fusemax_attention(
        q, k, v,
        causal=cfg.causal,
        window=spec.window,
        softcap=cfg.attn_softcap,
        impl=rt.attn_impl,
        block_q=rt.block_q,
        block_k=rt.block_k,
        exp_impl=rt.exp_impl,
        interpret=rt.interpret,
        unroll_scan=rt.unroll_runs,
    )                                                    # [B, H, S, dh]
    out = rt.shard_activation(out, ("batch", "heads", "seq", "head_dim"))
    return jnp.einsum("bhse,hed->bsd", out, p["wo"].astype(x.dtype))


def gqa_init_cache(cfg: ModelConfig, spec: LayerSpec, batch: int,
                   max_len: int, dtype) -> dict:
    slots = spec.window if spec.window is not None else max_len
    shape = (batch, cfg.n_kv_heads, slots, cfg.dh)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def gqa_prefill_chunk(
    p, x: jnp.ndarray, cache: dict, off: int,
    cfg: ModelConfig, spec: LayerSpec, rt: Runtime,
    true_len: Optional[jnp.ndarray] = None,
) -> tuple[jnp.ndarray, dict]:
    """Chunked-prefill continuation: queries [off, off+S) attend the cached
    history plus the chunk itself, and the chunk's K/V are written into the
    cache.  ``off`` is a static chunk offset (positions [0, off) must
    already be cached).  x: [B, S, d].  ``true_len`` (length-bucketed
    batches) masks ring writes past each row's real prompt length."""
    b, s_len, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(off, off + s_len), (b, s_len))
    q, k_new, v_new = _proj_qkv(p, x, cfg, positions, rt)
    kc, vc = cache["k"], cache["v"]
    slots = kc.shape[2]

    if spec.window is not None and true_len is not None:
        # ring + bucket padding: attend the gathered history band as the
        # unmasked path does; writes go through the shared masked ring
        # scatter
        w = spec.window
        klo = max(0, off - w + 1)
        hist = jnp.arange(klo, off)
        k_band = jnp.concatenate([kc[:, :, hist % slots], k_new], axis=2)
        v_band = jnp.concatenate([vc[:, :, hist % slots], v_new], axis=2)
        out = fusemax_attention(
            q, k_band, v_band,
            causal=cfg.causal, window=w, softcap=cfg.attn_softcap,
            q_offset=off - klo,
            impl=rt.attn_impl, block_q=rt.block_q, block_k=rt.block_k,
            exp_impl=rt.exp_impl, interpret=rt.interpret,
            unroll_scan=rt.unroll_runs,
        )
        kc, vc = ring_write_masked(kc, vc, k_new, v_new, off, true_len)
        y = jnp.einsum("bhse,hed->bsd", out, p["wo"].astype(x.dtype))
        return y, {"k": kc, "v": vc}

    if spec.window is None:
        kc = kc.at[:, :, off:off + s_len].set(k_new)
        vc = vc.at[:, :, off:off + s_len].set(v_new)
        out = fusemax_attention(
            q, kc[:, :, :off + s_len], vc[:, :, :off + s_len],
            causal=cfg.causal, softcap=cfg.attn_softcap, q_offset=off,
            impl=rt.attn_impl, block_q=rt.block_q, block_k=rt.block_k,
            exp_impl=rt.exp_impl, interpret=rt.interpret,
            unroll_scan=rt.unroll_runs,
        )
    else:
        # ring cache (slots == window): gather the still-needed history
        # band [klo, off) *before* overwriting ring slots with the chunk.
        w = spec.window
        klo = max(0, off - w + 1)
        hist = jnp.arange(klo, off)
        k_band = jnp.concatenate([kc[:, :, hist % slots], k_new], axis=2)
        v_band = jnp.concatenate([vc[:, :, hist % slots], v_new], axis=2)
        out = fusemax_attention(
            q, k_band, v_band,
            causal=cfg.causal, window=w, softcap=cfg.attn_softcap,
            q_offset=off - klo,
            impl=rt.attn_impl, block_q=rt.block_q, block_k=rt.block_k,
            exp_impl=rt.exp_impl, interpret=rt.interpret,
            unroll_scan=rt.unroll_runs,
        )
        if s_len >= slots:          # chunk alone wraps the ring: keep tail
            pos = jnp.arange(off + s_len - slots, off + s_len) % slots
            kc = kc.at[:, :, pos].set(k_new[:, :, -slots:])
            vc = vc.at[:, :, pos].set(v_new[:, :, -slots:])
        else:
            pos = jnp.arange(off, off + s_len) % slots
            kc = kc.at[:, :, pos].set(k_new)
            vc = vc.at[:, :, pos].set(v_new)
    y = jnp.einsum("bhse,hed->bsd", out, p["wo"].astype(x.dtype))
    return y, {"k": kc, "v": vc}


def gqa_decode(
    p, x: jnp.ndarray, cache: dict, kv_len: jnp.ndarray,
    cfg: ModelConfig, spec: LayerSpec, rt: Runtime,
) -> tuple[jnp.ndarray, dict]:
    """One-token decode. x: [B, 1, d]; kv_len: [B] length *including* x."""
    b = x.shape[0]
    pos = (kv_len - 1)[:, None]                          # [B, 1]
    q, k_new, v_new = _proj_qkv(p, x, cfg, pos, rt)      # [B, H*, 1, dh]

    slots = cache["k"].shape[2]
    slot = (pos % slots)[:, 0]                           # ring or linear
    bidx = jnp.arange(b)
    k_cache = cache["k"].at[bidx, :, slot].set(k_new[:, :, 0])
    v_cache = cache["v"].at[bidx, :, slot].set(v_new[:, :, 0])

    if spec.window is not None:
        eff_len = jnp.minimum(kv_len, slots)             # ring: all in-window
        win = None                                       # implied by ring
    else:
        eff_len = kv_len
        win = None
    out = fusemax_decode(
        q, k_cache, v_cache, eff_len,
        softcap=cfg.attn_softcap,
        window=win,
        impl=rt.attn_impl,
        splits=rt.decode_splits,
        exp_impl=rt.exp_impl,
        interpret=rt.interpret,
    )                                                    # [B, H, 1, dh]
    y = jnp.einsum("bhse,hed->bsd", out, p["wo"].astype(x.dtype))
    return y, {"k": k_cache, "v": v_cache}


# ---------------------------------------------------------------------------
# GQA — paged cache variants
# ---------------------------------------------------------------------------

def gqa_init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                         dtype, kv_dtype: Optional[str] = None) -> dict:
    shape = (num_pages, cfg.n_kv_heads, page_size, cfg.dh)
    qdt = kv_quant_dtype(kv_dtype)
    if qdt is None:
        return {"k_pages": jnp.zeros(shape, dtype),
                "v_pages": jnp.zeros(shape, dtype)}
    # quantized pool: narrow data pages + per-token-per-head fp16 scales
    return {"k_pages": jnp.zeros(shape, qdt),
            "v_pages": jnp.zeros(shape, qdt),
            "k_scale": jnp.ones(shape[:-1], jnp.float16),
            "v_scale": jnp.ones(shape[:-1], jnp.float16)}


def _gqa_capacity(cache: dict, bt_rows: jnp.ndarray,
                  spec: LayerSpec) -> int:
    """Logical token capacity of a paged GQA cache: the window for local
    layers (ring eviction), the full table span for global layers."""
    page_size = cache["k_pages"].shape[-2]
    return spec.window if spec.window is not None \
        else bt_rows.shape[1] * page_size


def _gqa_paged_attend(
    q: jnp.ndarray, k_new: jnp.ndarray, v_new: jnp.ndarray,
    k_pages: jnp.ndarray, v_pages: jnp.ndarray, bt_rows: jnp.ndarray,
    off: int, cap: int, cfg: ModelConfig, spec: LayerSpec, rt: Runtime,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Attention for a paged prefill chunk, *before* the chunk's writes
    land: queries [off, off+S) attend the cached history (gathered through
    the block-table rows) plus the chunk's own fresh K/V.  Returns the
    pre-output-projection attention output [B, H, S, F].

    Quantized pools pass their scale pools (``k_scale``/``v_scale``,
    [P, Hkv, ps]); the gathered history is dequantized here, and the
    caller supplies quant-round-tripped fresh K/V so the chunk attends
    exactly the values later reads will reconstruct.

    Every operation is independent per kv-head fiber, so this body runs
    unchanged on a kv-head *shard* of (q, k_new, v_new, pages, scales)
    under ``shard_map`` — the per-head arithmetic (and the autotuned
    tiles, which depend only on lengths and the unchanged head-group
    ratio) is bit-identical to the full-head call."""
    if off == 0:
        # no history: attend the chunk itself (matches gqa_forward)
        return fusemax_attention(
            q, k_new, v_new,
            causal=cfg.causal, window=spec.window, softcap=cfg.attn_softcap,
            impl=rt.attn_impl, block_q=rt.block_q, block_k=rt.block_k,
            exp_impl=rt.exp_impl, interpret=rt.interpret,
            unroll_scan=rt.unroll_runs,
        )
    if spec.window is None:
        # gather only the pages the prefix occupies (off is static)
        hp = -(-off // k_pages.shape[2])
        k_hist = gather_kv_pages(k_pages, bt_rows[:, :hp])[:, :, :off]
        v_hist = gather_kv_pages(v_pages, bt_rows[:, :hp])[:, :, :off]
        if k_scale is not None:
            k_hist = dequantize_kv(
                k_hist,
                gather_kv_pages(k_scale, bt_rows[:, :hp])[:, :, :off],
                k_new.dtype)
            v_hist = dequantize_kv(
                v_hist,
                gather_kv_pages(v_scale, bt_rows[:, :hp])[:, :, :off],
                v_new.dtype)
        # chunk K/V rounded to the cache dtype first — the dense path reads
        # them back out of the cache it just wrote
        return fusemax_attention(
            q, jnp.concatenate([k_hist, k_new.astype(k_hist.dtype)], axis=2),
            jnp.concatenate([v_hist, v_new.astype(v_hist.dtype)], axis=2),
            causal=cfg.causal, softcap=cfg.attn_softcap, q_offset=off,
            impl=rt.attn_impl, block_q=rt.block_q, block_k=rt.block_k,
            exp_impl=rt.exp_impl, interpret=rt.interpret,
            unroll_scan=rt.unroll_runs,
        )
    # ring continuation: gather the still-needed history band from the
    # ring pages before this chunk's writes land
    w = spec.window
    klo = max(0, off - w + 1)
    l = jnp.arange(klo, off) % cap
    page_size = k_pages.shape[2]
    pg = bt_rows[:, l // page_size][:, None]             # [B, 1, band]
    heads = jnp.arange(k_pages.shape[1])[None, :, None]
    slot = (l % page_size)[None, None]
    k_hist = k_pages[pg, heads, slot]                    # [B, Hkv, band, dh]
    v_hist = v_pages[pg, heads, slot]
    if k_scale is not None:
        k_hist = dequantize_kv(k_hist, k_scale[pg, heads, slot],
                               k_new.dtype)
        v_hist = dequantize_kv(v_hist, v_scale[pg, heads, slot],
                               v_new.dtype)
    return fusemax_attention(
        q, jnp.concatenate([k_hist, k_new], axis=2),
        jnp.concatenate([v_hist, v_new], axis=2),
        causal=cfg.causal, window=w, softcap=cfg.attn_softcap,
        q_offset=off - klo,
        impl=rt.attn_impl, block_q=rt.block_q, block_k=rt.block_k,
        exp_impl=rt.exp_impl, interpret=rt.interpret,
        unroll_scan=rt.unroll_runs,
    )


def _gqa_quant_new(cache: dict, k_new: jnp.ndarray, v_new: jnp.ndarray):
    """Quantize a chunk's fresh K/V ([B, Hkv, S, dh]) against the pool's
    storage dtype → (k_q, k_s, v_q, v_s, k_att, v_att): raw quantized
    values + per-token-per-head scales for the page writes, plus the
    round-tripped attend views (what later reads will reconstruct).
    Unquantized pools return the inputs unchanged with None scales.
    Quantization is per-(token, head), so a kv-head shard of the outputs
    equals quantizing the shard — callers may slice these under
    ``shard_map`` and stay bit-identical to the unsharded pool."""
    if "k_scale" not in cache:
        return k_new, None, v_new, None, k_new, v_new
    qdt = cache["k_pages"].dtype
    k_q, k_s = quantize_kv(k_new, qdt)
    v_q, v_s = quantize_kv(v_new, qdt)
    return (k_q, k_s, v_q, v_s,
            dequantize_kv(k_q, k_s, k_new.dtype),
            dequantize_kv(v_q, v_s, v_new.dtype))


def gqa_prefill_paged(
    p, x: jnp.ndarray, cache: dict, bt_rows: jnp.ndarray, off: int,
    cfg: ModelConfig, spec: LayerSpec, rt: Runtime,
    true_len: jnp.ndarray,
    cached_len: Optional[jnp.ndarray] = None,
) -> tuple[jnp.ndarray, dict]:
    """Prefill a prompt chunk straight into the page pool (no dense
    mini-cache): queries [off, off+S) attend history gathered through the
    block-table rows plus the chunk itself; the chunk's K/V scatter into
    pages, masked by ``true_len``.  ``cached_len`` ([B] int32) marks each
    row's shared-prefix extent: positions below it live in pages mapped
    from the prefix index and must be read but never rewritten, so their
    writes are dropped too.  Outputs are bit-identical to the dense
    prefill path — the attention inputs are the same arrays, only the
    K/V residency differs.

    With ``rt.kv_shard`` the whole attend+write body runs under
    ``shard_map``: each device handles its kv-head slice of the pool and
    the head outputs are all-gathered before the (replicated) output
    projection — see the module docstring."""
    b, s_len, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(off, off + s_len), (b, s_len))
    cap = _gqa_capacity(cache, bt_rows, spec)
    tl = true_len[:, None]
    pos = positions[:1]                                  # [1, S]
    valid = (pos < tl) & (pos >= jnp.minimum(tl, off + s_len) - cap)
    if cached_len is not None:
        valid = valid & (positions >= cached_len[:, None])
    valid = jnp.broadcast_to(valid, positions.shape)

    shard = rt.kv_shard
    if shard is not None:
        q, k_new, v_new = _proj_qkv(p, x, cfg, positions, rt)
        k_q, k_s, v_q, v_s, k_att, v_att = _gqa_quant_new(cache, k_new,
                                                          v_new)
        pspec = shard.spec(4, 1)                         # pages: Hkv axis
        hspec = shard.spec(4, 1)                         # [B, H*, S, E]
        rep = shard.replicated

        if k_s is not None:
            sspec = shard.spec(3, 1)                     # scales: Hkv axis
            hspec3 = shard.spec(3, 1)                    # [B, Hkv, S]

            def local_q(kp, vp, ksp, vsp, q_l, ka_l, va_l, kq_l, vq_l,
                        ks_l, vs_l, bt, pos_b, val):
                out = _gqa_paged_attend(q_l, ka_l, va_l, kp, vp, bt, off,
                                        cap, cfg, spec, rt,
                                        k_scale=ksp, v_scale=vsp)
                kp = write_kv_pages(kp, bt, pos_b, kq_l,
                                 cap, val)
                vp = write_kv_pages(vp, bt, pos_b, vq_l,
                                 cap, val)
                ksp = write_kv_pages(ksp, bt, pos_b, ks_l,
                                  cap, val)
                vsp = write_kv_pages(vsp, bt, pos_b, vs_l,
                                  cap, val)
                out = jax.lax.all_gather(out, shard.axis, axis=1,
                                         tiled=True)
                return out, kp, vp, ksp, vsp

            out, k_pages, v_pages, k_sc, v_sc = jax.shard_map(
                local_q, mesh=shard.mesh, check_vma=False,
                in_specs=(pspec, pspec, sspec, sspec, hspec, hspec, hspec,
                          hspec, hspec, hspec3, hspec3, rep, rep, rep),
                out_specs=(rep, pspec, pspec, sspec, sspec),
            )(cache["k_pages"], cache["v_pages"], cache["k_scale"],
              cache["v_scale"], q, k_att, v_att, k_q, v_q, k_s, v_s,
              bt_rows, positions, valid)
            y = jnp.einsum("bhse,hed->bsd", out, p["wo"].astype(x.dtype))
            return y, {"k_pages": k_pages, "v_pages": v_pages,
                       "k_scale": k_sc, "v_scale": v_sc}

        def local(kp, vp, q_l, kn_l, vn_l, bt, pos_b, val):
            out = _gqa_paged_attend(q_l, kn_l, vn_l, kp, vp, bt, off, cap,
                                    cfg, spec, rt)
            kp = write_kv_pages(kp, bt, pos_b, kn_l, cap,
                             val)
            vp = write_kv_pages(vp, bt, pos_b, vn_l, cap,
                             val)
            out = jax.lax.all_gather(out, shard.axis, axis=1, tiled=True)
            return out, kp, vp

        out, k_pages, v_pages = jax.shard_map(
            local, mesh=shard.mesh, check_vma=False,
            in_specs=(pspec, pspec, hspec, hspec, hspec, rep, rep, rep),
            out_specs=(rep, pspec, pspec),
        )(cache["k_pages"], cache["v_pages"], q, k_new, v_new, bt_rows,
          positions, valid)
        y = jnp.einsum("bhse,hed->bsd", out, p["wo"].astype(x.dtype))
        return y, {"k_pages": k_pages, "v_pages": v_pages}

    if off == 0:
        y = gqa_forward(p, x, cfg, spec, rt)
        _, k_new, v_new = _proj_qkv(p, x, cfg, positions, rt)
        k_q, k_s, v_q, v_s, _, _ = _gqa_quant_new(cache, k_new, v_new)
    else:
        q, k_new, v_new = _proj_qkv(p, x, cfg, positions, rt)
        k_q, k_s, v_q, v_s, k_att, v_att = _gqa_quant_new(cache, k_new,
                                                          v_new)
        out = _gqa_paged_attend(q, k_att, v_att, cache["k_pages"],
                                cache["v_pages"], bt_rows, off, cap, cfg,
                                spec, rt, k_scale=cache.get("k_scale"),
                                v_scale=cache.get("v_scale"))
        y = jnp.einsum("bhse,hed->bsd", out, p["wo"].astype(x.dtype))

    new_cache = {
        "k_pages": write_kv_pages(cache["k_pages"], bt_rows, positions,
                               k_q, cap, valid),
        "v_pages": write_kv_pages(cache["v_pages"], bt_rows, positions,
                               v_q, cap, valid),
    }
    if k_s is not None:
        new_cache["k_scale"] = write_kv_pages(
            cache["k_scale"], bt_rows, positions,
            k_s, cap, valid)
        new_cache["v_scale"] = write_kv_pages(
            cache["v_scale"], bt_rows, positions,
            v_s, cap, valid)
    return y, new_cache


def _gqa_write_attend(pools: dict, new: dict, layer, q, bt_rows, pos,
                      valid, kv_len, cap: int, capacity: Optional[int],
                      cfg: ModelConfig, rt: Runtime):
    """Write a step's K/V (and scale) rows into layer ``layer`` of the
    carried pools (:func:`write_page_rows`), then run the paged decode
    kernel on them → (attention out [B, H, P, dh], updated pools).  Per
    kv head throughout, so it runs unchanged on a kv-head shard under
    ``shard_map``."""
    pools = _write_rows(pools, new, layer, bt_rows, pos, cap, valid, tok=1)
    out = fusemax_decode_paged(
        q, pools["k_pages"], pools["v_pages"], bt_rows, kv_len,
        layer=layer,
        capacity=capacity,
        softcap=cfg.attn_softcap,
        impl=rt.attn_impl,
        splits=rt.decode_splits,
        exp_impl=rt.exp_impl,
        interpret=rt.interpret,
        k_scale=pools.get("k_scale"),
        v_scale=pools.get("v_scale"),
    )
    return out, pools


def _gqa_new_rows(cache: dict, k_new: jnp.ndarray,
                  v_new: jnp.ndarray) -> dict:
    """Pool name → the rows a step writes (quantized with their scales
    where the pool is; see :func:`_gqa_quant_new`)."""
    k_q, k_s, v_q, v_s, _, _ = _gqa_quant_new(cache, k_new, v_new)
    new = {"k_pages": k_q, "v_pages": v_q}
    if k_s is not None:
        new.update(k_scale=k_s, v_scale=v_s)
    return new


def gqa_decode_paged(
    p, x: jnp.ndarray, cache: dict, bt_rows: jnp.ndarray,
    kv_len: jnp.ndarray, cfg: ModelConfig, spec: LayerSpec, rt: Runtime,
    layer,
) -> tuple[jnp.ndarray, dict]:
    """One-token decode as layer ``layer`` of pools stacked over a run's
    layers (``cache`` leaves [L, P, Hkv, ps, dh], scales [L, P, Hkv, ps]):
    write the new K/V rows in place at the logical tail (ring-wrapped for
    local layers), read through the block table.  Inactive slots
    (kv_len == 0) write nothing — their table rows may hold the sentinel
    page.

    With ``rt.kv_shard`` the write + split-K decode run head-parallel
    under ``shard_map`` (each device decodes its kv-head slice of the
    pool against the full, replicated block table), and head outputs are
    all-gathered before the replicated output projection."""
    pos = (kv_len - 1)[:, None]                          # [B, 1]
    q, k_new, v_new = _proj_qkv(p, x, cfg, pos, rt)      # [B, H*, 1, dh]
    cap = _gqa_capacity(cache, bt_rows, spec)
    valid = (kv_len > 0)[:, None]

    if spec.window is not None:
        eff_len = jnp.minimum(kv_len, cap)               # ring: all in-window
        capacity = cap
    else:
        eff_len = kv_len
        capacity = None

    new = _gqa_new_rows(cache, k_new, v_new)
    pools = {k: cache[k] for k in new}
    shard = rt.kv_shard
    if shard is None:
        out, pools = _gqa_write_attend(pools, new, layer, q, bt_rows, pos,
                                       valid, eff_len, cap, capacity, cfg,
                                       rt)
    else:
        def local(pools, new_l, q_l, bt, pos_b, val, el, lyr):
            out, pools = _gqa_write_attend(pools, new_l, lyr, q_l, bt,
                                           pos_b, val, el, cap, capacity,
                                           cfg, rt)
            out = jax.lax.all_gather(out, shard.axis, axis=1, tiled=True)
            return out, pools

        # pages and scales shard their Hkv axis (third / second from the
        # right, as ``sharding.paged_cache_shardings`` places them); the
        # new rows and q shard their head axis
        pspecs = {k: shard.spec(a.ndim, -3 if k.endswith("_pages") else -2)
                  for k, a in pools.items()}
        nspecs = {k: shard.spec(a.ndim, 1) for k, a in new.items()}
        rep = shard.replicated
        out, pools = jax.shard_map(
            local, mesh=shard.mesh, check_vma=False,
            in_specs=(pspecs, nspecs, shard.spec(4, 1), rep, rep, rep, rep,
                      rep),
            out_specs=(rep, pspecs),
        )(pools, new, q, bt_rows, pos, valid, eff_len,
          jnp.asarray(layer, jnp.int32))
    y = jnp.einsum("bhse,hed->bsd", out, p["wo"].astype(x.dtype))
    return y, pools


def gqa_verify(
    p, x: jnp.ndarray, cache: dict, kv_len: jnp.ndarray,
    span: jnp.ndarray, cfg: ModelConfig, spec: LayerSpec, rt: Runtime,
) -> tuple[jnp.ndarray, dict]:
    """Speculative verify: score a P-token draft chain in one dispatch.

    x: [B, P, d] — position j of the chain sits at absolute position
    ``kv_len - 1 + j`` (``kv_len`` counts the cache *including* chain
    position 0, exactly as :func:`gqa_decode`'s contract).  ``span``: [B]
    number of real chain positions per row — K/V writes beyond it drop,
    so rejected drafts never pollute the cache, and outputs beyond it
    are garbage the engine ignores.  Global attention only (the engine
    gates speculation off for windowed layers)."""
    b, pq, _ = x.shape
    pos = (kv_len - 1)[:, None] + jnp.arange(pq)[None]   # [B, P] absolute
    q, k_new, v_new = _proj_qkv(p, x, cfg, pos, rt)      # [B, H*, P, dh]

    slots = cache["k"].shape[2]
    valid = jnp.arange(pq)[None] < span[:, None]
    slot_idx = jnp.where(valid, pos, slots)              # OOB → dropped
    bidx = jnp.arange(b)[:, None]
    k_cache = cache["k"].at[bidx, :, slot_idx].set(
        jnp.moveaxis(k_new, 1, 2), mode="drop")
    v_cache = cache["v"].at[bidx, :, slot_idx].set(
        jnp.moveaxis(v_new, 1, 2), mode="drop")

    out = fusemax_decode(
        q, k_cache, v_cache, kv_len,
        softcap=cfg.attn_softcap,
        impl=rt.attn_impl,
        splits=rt.decode_splits,
        exp_impl=rt.exp_impl,
        interpret=rt.interpret,
    )                                                    # [B, H, P, dh]
    y = jnp.einsum("bhse,hed->bsd", out, p["wo"].astype(x.dtype))
    return y, {"k": k_cache, "v": v_cache}


def gqa_verify_paged(
    p, x: jnp.ndarray, cache: dict, bt_rows: jnp.ndarray,
    kv_len: jnp.ndarray, span: jnp.ndarray,
    cfg: ModelConfig, spec: LayerSpec, rt: Runtime, layer,
) -> tuple[jnp.ndarray, dict]:
    """Paged :func:`gqa_verify` as layer ``layer`` of stacked pools (see
    :func:`gqa_decode_paged`): chain K/V rows land through the block
    table (the tail rows are the slot's scratch draft pages — see
    ``PagedKVCache.reserve_draft``), the verify kernel reads back through
    the same table.  Unsharded only (the engine gates speculation off
    under a device mesh)."""
    b, pq, _ = x.shape
    pos = (kv_len - 1)[:, None] + jnp.arange(pq)[None]   # [B, P]
    q, k_new, v_new = _proj_qkv(p, x, cfg, pos, rt)      # [B, H*, P, dh]
    cap = _gqa_capacity(cache, bt_rows, spec)
    valid = (jnp.arange(pq)[None] < span[:, None]) & (kv_len > 0)[:, None]

    new = _gqa_new_rows(cache, k_new, v_new)
    out, pools = _gqa_write_attend({k: cache[k] for k in new}, new, layer,
                                   q, bt_rows, pos, valid, kv_len, cap, None,
                                   cfg, rt)              # [B, H, P, dh]
    y = jnp.einsum("bhse,hed->bsd", out, p["wo"].astype(x.dtype))
    return y, pools


# ---------------------------------------------------------------------------
# MLA (DeepSeek multi-head latent attention)
# ---------------------------------------------------------------------------

def mla_init(key, cfg: ModelConfig, dtype=jnp.float32):
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qk = m.nope_dim + m.rope_dim
    ks = jax.random.split(key, 6)
    params = {
        "w_dq": _init(ks[0], (d, m.q_lora_rank), 1 / math.sqrt(d), dtype),
        "w_uq": _init(ks[1], (m.q_lora_rank, h, qk),
                      1 / math.sqrt(m.q_lora_rank), dtype),
        "w_dkv": _init(ks[2], (d, m.kv_lora_rank + m.rope_dim),
                       1 / math.sqrt(d), dtype),
        "w_uk": _init(ks[3], (m.kv_lora_rank, h, m.nope_dim),
                      1 / math.sqrt(m.kv_lora_rank), dtype),
        "w_uv": _init(ks[4], (m.kv_lora_rank, h, m.v_dim),
                      1 / math.sqrt(m.kv_lora_rank), dtype),
        "wo": _init(ks[5], (h, m.v_dim, d), 1 / math.sqrt(h * m.v_dim),
                    dtype),
    }
    axes = {
        "w_dq": ("embed", "latent"),
        "w_uq": ("latent", "heads", "head_dim"),
        "w_dkv": ("embed", "latent"),
        "w_uk": ("latent", "heads", "head_dim"),
        "w_uv": ("latent", "heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    qn, qna = norm_init(m.q_lora_rank, "rmsnorm", dtype)
    kn, kna = norm_init(m.kv_lora_rank, "rmsnorm", dtype)
    params["q_norm"], axes["q_norm"] = qn, qna
    params["kv_norm"], axes["kv_norm"] = kn, kna
    # q_norm/kv_norm scales live on the latent axis, not embed
    axes["q_norm"] = {"scale": ("latent",)}
    axes["kv_norm"] = {"scale": ("latent",)}
    return params, axes


def _mla_qkv_latent(p, x, cfg: ModelConfig, positions):
    """Shared down-projections: returns (q_nope, q_rope, ckv, k_rope)."""
    m = cfg.mla
    dt = x.dtype
    cq = apply_norm(p["q_norm"], x @ p["w_dq"].astype(dt))
    q = jnp.einsum("bsr,rhe->bhse", cq, p["w_uq"].astype(dt))
    q_nope, q_rope = q[..., : m.nope_dim], q[..., m.nope_dim:]
    q_rope = rope(q_rope, positions[:, None, :], cfg.rope_theta)

    dkv = x @ p["w_dkv"].astype(dt)                      # [B,S,r+rd]
    ckv = apply_norm(p["kv_norm"], dkv[..., : m.kv_lora_rank])
    k_rope = rope(dkv[..., m.kv_lora_rank:], positions, cfg.rope_theta)
    return q_nope, q_rope, ckv, k_rope


def mla_forward(
    p, x: jnp.ndarray, cfg: ModelConfig, spec: LayerSpec, rt: Runtime,
    positions: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Training/prefill MLA: expand latents per head, run FuseMax."""
    m = cfg.mla
    b, s_len, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s_len), (b, s_len))
    q_nope, q_rope, ckv, k_rope = _mla_qkv_latent(p, x, cfg, positions)
    dt = x.dtype
    k_nope = jnp.einsum("bsr,rhe->bhse", ckv, p["w_uk"].astype(dt))
    v = jnp.einsum("bsr,rhe->bhse", ckv, p["w_uv"].astype(dt))
    h = cfg.n_heads
    q = jnp.concatenate([q_nope, q_rope], axis=-1)       # [B,H,S,qk]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, None], (b, h, s_len, m.rope_dim))],
        axis=-1,
    )
    q = rt.shard_activation(q, ("batch", "heads", "seq", "head_dim"))
    k = rt.shard_activation(k, ("batch", "heads", "seq", "head_dim"))
    out = fusemax_attention(
        q, k, v,
        causal=cfg.causal,
        softcap=cfg.attn_softcap,
        scale=1.0 / math.sqrt(m.nope_dim + m.rope_dim),
        impl=rt.attn_impl,
        block_q=rt.block_q,
        block_k=rt.block_k,
        exp_impl=rt.exp_impl,
        interpret=rt.interpret,
        unroll_scan=rt.unroll_runs,
    )
    out = rt.shard_activation(out, ("batch", "heads", "seq", "head_dim"))
    return jnp.einsum("bhse,hed->bsd", out, p["wo"].astype(dt))


def _mla_absorbed_attend(
    p, q_nope: jnp.ndarray, q_rope: jnp.ndarray,
    ckv: jnp.ndarray, krope: jnp.ndarray, off: int,
    cfg: ModelConfig, rt: Runtime,
) -> jnp.ndarray:
    """Absorbed-form chunk attention over a latent history (Hkv=1 fiber,
    group = every q head): W_uk folds into the queries once per chunk
    (``q_eff = q_nopeᵀW_uk``, resident across the whole chunk), scores hit
    the rank-r latents + shared rope keys directly, and the accumulator
    stays in latent space until the final W_uv lift — the per-head K/V
    expansion of the history never exists, so chunked prefill bounds peak
    activations on MLA layers exactly as it does on GQA.

    ckv: [B, tot, r]; krope: [B, tot, rd] (history including this chunk).
    Returns the per-head output [B, H, S, v_dim] (pre-``wo``)."""
    m = cfg.mla
    dt = q_nope.dtype
    q_eff = jnp.einsum("bhse,rhe->bhsr", q_nope, p["w_uk"].astype(dt))
    q_cat = jnp.concatenate([q_eff, q_rope], axis=-1)    # [B,H,S,r+rd]
    k_cat = jnp.concatenate([ckv, krope], axis=-1)[:, None]
    v_lat = ckv[:, None]                                 # [B,1,tot,r]
    out_lat = fusemax_attention(
        q_cat, k_cat, v_lat,
        causal=cfg.causal, softcap=cfg.attn_softcap,
        scale=1.0 / math.sqrt(m.nope_dim + m.rope_dim), q_offset=off,
        impl=rt.attn_impl, block_q=rt.block_q, block_k=rt.block_k,
        exp_impl=rt.exp_impl, interpret=rt.interpret,
        unroll_scan=rt.unroll_runs,
    )                                                    # [B,H,S,r]
    return jnp.einsum("bhsr,rhe->bhse", out_lat, p["w_uv"].astype(dt))


def mla_prefill_chunk(
    p, x: jnp.ndarray, cache: dict, off: int,
    cfg: ModelConfig, spec: LayerSpec, rt: Runtime,
) -> tuple[jnp.ndarray, dict]:
    """Chunked-prefill continuation for MLA: the chunk's latents are written
    at [off, off+S) and queries attend the full cached prefix in absorbed
    form (:func:`_mla_absorbed_attend`) — the prefix stays latent
    ([tot, r + rd] per sequence instead of [H, tot, nope + rope_dim + v]),
    so ``prefill_chunk`` bounds peak activations on MLA layers too."""
    b, s_len, _ = x.shape
    dt = x.dtype
    positions = jnp.broadcast_to(jnp.arange(off, off + s_len), (b, s_len))
    q_nope, q_rope, ckv_new, krope_new = _mla_qkv_latent(p, x, cfg, positions)
    ckv = cache["ckv"].at[:, off:off + s_len].set(ckv_new)
    krope = cache["krope"].at[:, off:off + s_len].set(krope_new)

    tot = off + s_len
    out = _mla_absorbed_attend(p, q_nope, q_rope, ckv[:, :tot],
                               krope[:, :tot], off, cfg, rt)
    y = jnp.einsum("bhse,hed->bsd", out, p["wo"].astype(dt))
    return y, {"ckv": ckv, "krope": krope}


def mla_init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    m = cfg.mla
    return {
        "ckv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        "krope": jnp.zeros((batch, max_len, m.rope_dim), dtype),
    }


def mla_decode(
    p, x: jnp.ndarray, cache: dict, kv_len: jnp.ndarray,
    cfg: ModelConfig, spec: LayerSpec, rt: Runtime,
) -> tuple[jnp.ndarray, dict]:
    """Absorbed-form decode: attention in latent space (Hkv=1, group=H).

    Scores:  s[h, t] = q_nopeᵀ W_uk[h] · ckv_t + q_ropeᵀ · krope_t
    Values:  out[h]  = (Σ_t a[h,t] ckv_t) W_uv[h]
    The cache stores only the rank-r latent + shared rope key per token —
    the MLA memory win — and FuseMax decode handles the Hkv=1 fiber.
    """
    m = cfg.mla
    b = x.shape[0]
    dt = x.dtype
    pos = (kv_len - 1)[:, None]
    q_nope, q_rope, ckv_new, krope_new = _mla_qkv_latent(p, x, cfg, pos)

    bidx = jnp.arange(b)
    slot = pos[:, 0]
    ckv = cache["ckv"].at[bidx, slot].set(ckv_new[:, 0])
    krope = cache["krope"].at[bidx, slot].set(krope_new[:, 0])

    # absorb W_uk into q: q_eff[h] ∈ R^{kv_lora_rank}
    q_eff = jnp.einsum("bhse,rhe->bhsr", q_nope, p["w_uk"].astype(dt))
    q_cat = jnp.concatenate([q_eff, q_rope], axis=-1)    # [B,H,1,r+rd]
    k_cat = jnp.concatenate([ckv, krope], axis=-1)[:, None]  # [B,1,M,r+rd]
    v_lat = ckv[:, None]                                 # [B,1,M,r]

    out_lat = fusemax_decode(
        q_cat, k_cat, v_lat, kv_len,
        scale=1.0 / math.sqrt(m.nope_dim + m.rope_dim),
        softcap=cfg.attn_softcap,
        impl=rt.attn_impl,
        splits=rt.decode_splits,
        exp_impl=rt.exp_impl,
        interpret=rt.interpret,
    )                                                    # [B,H,1,r]
    out = jnp.einsum("bhsr,rhe->bhse", out_lat, p["w_uv"].astype(dt))
    y = jnp.einsum("bhse,hed->bsd", out, p["wo"].astype(dt))
    return y, {"ckv": ckv, "krope": krope}


def mla_verify(
    p, x: jnp.ndarray, cache: dict, kv_len: jnp.ndarray,
    span: jnp.ndarray, cfg: ModelConfig, spec: LayerSpec, rt: Runtime,
) -> tuple[jnp.ndarray, dict]:
    """Speculative verify in latent space: the P-chain analogue of
    :func:`mla_decode` (see :func:`gqa_verify` for the chain contract)."""
    m = cfg.mla
    b, pq, _ = x.shape
    dt = x.dtype
    pos = (kv_len - 1)[:, None] + jnp.arange(pq)[None]   # [B, P]
    q_nope, q_rope, ckv_new, krope_new = _mla_qkv_latent(p, x, cfg, pos)

    slots = cache["ckv"].shape[1]
    valid = jnp.arange(pq)[None] < span[:, None]
    slot_idx = jnp.where(valid, pos, slots)              # OOB → dropped
    bidx = jnp.arange(b)[:, None]
    ckv = cache["ckv"].at[bidx, slot_idx].set(ckv_new, mode="drop")
    krope = cache["krope"].at[bidx, slot_idx].set(krope_new, mode="drop")

    q_eff = jnp.einsum("bhse,rhe->bhsr", q_nope, p["w_uk"].astype(dt))
    q_cat = jnp.concatenate([q_eff, q_rope], axis=-1)    # [B,H,P,r+rd]
    k_cat = jnp.concatenate([ckv, krope], axis=-1)[:, None]  # [B,1,M,r+rd]
    v_lat = ckv[:, None]                                 # [B,1,M,r]

    out_lat = fusemax_decode(
        q_cat, k_cat, v_lat, kv_len,
        scale=1.0 / math.sqrt(m.nope_dim + m.rope_dim),
        softcap=cfg.attn_softcap,
        impl=rt.attn_impl,
        splits=rt.decode_splits,
        exp_impl=rt.exp_impl,
        interpret=rt.interpret,
    )                                                    # [B,H,P,r]
    out = jnp.einsum("bhsr,rhe->bhse", out_lat, p["w_uv"].astype(dt))
    y = jnp.einsum("bhse,hed->bsd", out, p["wo"].astype(dt))
    return y, {"ckv": ckv, "krope": krope}


# ---------------------------------------------------------------------------
# MLA — paged cache variants
# ---------------------------------------------------------------------------

def mla_init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                         dtype, kv_dtype: Optional[str] = None) -> dict:
    m = cfg.mla
    qdt = kv_quant_dtype(kv_dtype)
    if qdt is None:
        return {
            "ckv_pages": jnp.zeros((num_pages, page_size, m.kv_lora_rank),
                                   dtype),
            "krope_pages": jnp.zeros((num_pages, page_size, m.rope_dim),
                                     dtype),
        }
    # quantized latent pool: per-token fp16 scales over the full vector
    return {
        "ckv_pages": jnp.zeros((num_pages, page_size, m.kv_lora_rank), qdt),
        "krope_pages": jnp.zeros((num_pages, page_size, m.rope_dim), qdt),
        "ckv_scale": jnp.ones((num_pages, page_size), jnp.float16),
        "krope_scale": jnp.ones((num_pages, page_size), jnp.float16),
    }


def _mla_quant_new(cache: dict, ckv_new: jnp.ndarray,
                   krope_new: jnp.ndarray):
    """Quantize a chunk's fresh latents ([B, S, r] / [B, S, rd]) against
    the pool's storage dtype → (ckv_q, ckv_s, kr_q, kr_s) with per-token
    scales over the full vector; unquantized pools pass through with None
    scales.  The scale reduction crosses the rank axis, so under a
    rank-sharded pool this MUST run outside ``shard_map`` on the full
    replicated values (each device then slices the identical quantized
    array — bit-identical to the unsharded pool by construction)."""
    if "ckv_scale" not in cache:
        return ckv_new, None, krope_new, None
    qdt = cache["ckv_pages"].dtype
    ckv_q, ckv_s = quantize_kv(ckv_new, qdt)
    kr_q, kr_s = quantize_kv(krope_new, qdt)
    return ckv_q, ckv_s, kr_q, kr_s


def _mla_write_scales(cache: dict, bt_rows, positions, ckv_s, kr_s, cap,
                      valid) -> dict:
    """Scatter per-token latent scales into the (replicated) scale pools.
    Runs outside any ``shard_map`` — the [P, ps] scale pools carry no
    rank axis, so every device holds the full copy."""
    return {
        "ckv_scale": write_pages(cache["ckv_scale"], bt_rows, positions,
                                 ckv_s, cap, valid),
        "krope_scale": write_pages(cache["krope_scale"], bt_rows,
                                   positions, kr_s, cap, valid),
    }


def mla_prefill_paged(
    p, x: jnp.ndarray, cache: dict, bt_rows: jnp.ndarray, off: int,
    cfg: ModelConfig, spec: LayerSpec, rt: Runtime,
    true_len: jnp.ndarray,
    cached_len: Optional[jnp.ndarray] = None,
) -> tuple[jnp.ndarray, dict]:
    """Prefill a prompt chunk's latents straight into the page pool; the
    chunk's queries attend the full cached prefix gathered through the
    block-table rows in absorbed form (:func:`_mla_absorbed_attend` —
    the W_uk-absorbed queries stay resident across the chunk and the
    prefix is never re-expanded to per-head K/V, mirroring
    :func:`mla_prefill_chunk`).  ``cached_len`` masks writes below each
    row's shared-prefix extent (see :func:`gqa_prefill_paged`).

    With ``rt.kv_shard`` the latent pages are partitioned along the rank
    axis: each device writes its rank-slice, and the history view is
    all-gathered back to the full rank *inside* the mapped region so the
    absorbed attention runs replicated (prefill happens once per prompt;
    the per-step FLOP sharding lives in :func:`mla_decode_paged`)."""
    b, s_len, _ = x.shape
    dt = x.dtype
    positions = jnp.broadcast_to(jnp.arange(off, off + s_len), (b, s_len))
    q_nope, q_rope, ckv_new, krope_new = _mla_qkv_latent(p, x, cfg,
                                                         positions)
    cap = bt_rows.shape[1] * cache["ckv_pages"].shape[1]
    valid = positions[:1] < true_len[:, None]
    if cached_len is not None:
        valid = valid & (positions >= cached_len[:, None])
    valid = jnp.broadcast_to(valid, positions.shape)
    tot = off + s_len
    # gather only the pages the prefix + chunk occupy (tot is static)
    hp = -(-tot // cache["ckv_pages"].shape[1])

    # quantization (full-vector scales) and the replicated scale-pool
    # writes happen outside any shard_map — see the helpers' contracts
    ckv_q, ckv_s, kr_q, kr_s = _mla_quant_new(cache, ckv_new, krope_new)
    scale_new = {} if ckv_s is None else _mla_write_scales(
        cache, bt_rows, positions, ckv_s, kr_s, cap, valid)

    shard = rt.kv_shard
    if shard is not None:
        def local(cp, krp, cn_l, kn_l, bt, pos_b, val):
            cp = write_pages(cp, bt, pos_b, cn_l, cap, val)
            krp = write_pages(krp, bt, pos_b, kn_l, cap, val)
            if off == 0:
                return cp, krp
            ckv_l = gather_pages(cp, bt[:, :hp])[:, :tot]
            kr_l = gather_pages(krp, bt[:, :hp])[:, :tot]
            ckv = jax.lax.all_gather(ckv_l, shard.axis, axis=2, tiled=True)
            kr = jax.lax.all_gather(kr_l, shard.axis, axis=2, tiled=True)
            return cp, krp, ckv, kr

        pspec = shard.spec(3, -1)                        # rank axis
        rep = shard.replicated
        outs = ((pspec, pspec) if off == 0
                else (pspec, pspec, rep, rep))
        got = jax.shard_map(
            local, mesh=shard.mesh, check_vma=False,
            in_specs=(pspec, pspec, pspec, pspec, rep, rep, rep),
            out_specs=outs,
        )(cache["ckv_pages"], cache["krope_pages"], ckv_q, kr_q,
          bt_rows, positions, valid)
        if off == 0:
            ckv_pages, krope_pages = got
            y = mla_forward(p, x, cfg, spec, rt)
            return y, {"ckv_pages": ckv_pages,
                       "krope_pages": krope_pages, **scale_new}
        ckv_pages, krope_pages, ckv, krope = got
    else:
        ckv_pages = write_pages(cache["ckv_pages"], bt_rows, positions,
                                ckv_q, cap, valid)
        krope_pages = write_pages(cache["krope_pages"], bt_rows, positions,
                                  kr_q, cap, valid)
        if off == 0:
            y = mla_forward(p, x, cfg, spec, rt)
            return y, {"ckv_pages": ckv_pages,
                       "krope_pages": krope_pages, **scale_new}
        ckv = gather_pages(ckv_pages, bt_rows[:, :hp])[:, :tot]
        krope = gather_pages(krope_pages, bt_rows[:, :hp])[:, :tot]
    if ckv_s is not None:
        # the gathered view includes the chunk just written, so dequant
        # against the *updated* scale pools
        ckv = dequantize_kv(
            ckv, gather_pages(scale_new["ckv_scale"],
                              bt_rows[:, :hp])[:, :tot], dt)
        krope = dequantize_kv(
            krope, gather_pages(scale_new["krope_scale"],
                                bt_rows[:, :hp])[:, :tot], dt)
    out = _mla_absorbed_attend(p, q_nope, q_rope, ckv, krope, off, cfg, rt)
    y = jnp.einsum("bhse,hed->bsd", out, p["wo"].astype(dt))
    return y, {"ckv_pages": ckv_pages, "krope_pages": krope_pages,
               **scale_new}


def mla_decode_paged(
    p, x: jnp.ndarray, cache: dict, bt_rows: jnp.ndarray,
    kv_len: jnp.ndarray, cfg: ModelConfig, spec: LayerSpec, rt: Runtime,
    layer,
) -> tuple[jnp.ndarray, dict]:
    """Absorbed-form decode against paged latents, one split per page, as
    layer ``layer`` of pools stacked over a run's layers (``cache`` leaves
    [L, P, ps, r], scales [L, P, ps]; rows written in place as in
    :func:`gqa_decode_paged`).

    Unsharded, the step dispatches to
    :func:`repro.kernels.ops.fusemax_mla_decode_paged`: on TPU the true
    paged Pallas kernel (block-table lookup in the ``index_map`` — the
    full latent table view is never materialized), elsewhere the per-page
    jnp split-K sweep over the slot's gathered pages.

    With ``rt.kv_shard`` the decode *FLOPs* shard, not just the bytes:
    each device writes its rank-slice of the pages, all-gathers the
    rank-complete history views (the storage bridge), then sweeps only
    its contiguous ``W/tp`` strip of block-table pages —
    :func:`repro.kernels.ops.mla_decode_partials` with a traced
    ``axis_index`` page offset.  The page-ordered (RM, RD, RNV) partial
    stacks are all-gathered (device order == page order on a 1-axis
    mesh) and the associative running-max combine runs replicated on
    identical operands, so the sharded stream is bit-identical to the
    unsharded per-page sweep while per-device attention FLOPs are 1/tp.
    Requires ``W % tp == 0`` (validated at engine construction).

    The split structure is fixed by the page geometry on every path
    (that is what makes unsharded and sharded streams match), so
    ``rt.decode_splits`` does not apply to MLA paged decode."""
    m = cfg.mla
    dt = x.dtype
    pos = (kv_len - 1)[:, None]
    q_nope, q_rope, ckv_new, krope_new = _mla_qkv_latent(p, x, cfg, pos)
    page_size = cache["ckv_pages"].shape[2]
    w = bt_rows.shape[1]
    cap = w * page_size
    valid = (kv_len > 0)[:, None]
    scale = 1.0 / math.sqrt(m.nope_dim + m.rope_dim)

    q_eff = jnp.einsum("bhse,rhe->bhsr", q_nope, p["w_uk"].astype(dt))
    q_cat = jnp.concatenate([q_eff, q_rope], axis=-1)    # [B,H,1,r+rd]

    # latent scales span the full (sharded) rank axis, so quantization
    # and the replicated scale-pool writes happen outside shard_map
    ckv_q, ckv_s, kr_q, kr_s = _mla_quant_new(cache, ckv_new, krope_new)
    scale_new = {} if ckv_s is None else _write_rows(
        cache, {"ckv_scale": ckv_s, "krope_scale": kr_s}, layer, bt_rows,
        pos, cap, valid, tok=0)

    shard = rt.kv_shard
    if shard is not None:
        sp = w // shard.size                 # pages swept per device

        def local(cp, krp, cn_l, kn_l, qc, bt, pos_b, val, kvl, lyr,
                  csp=None, krsp=None):
            got = _write_rows({"ckv_pages": cp, "krope_pages": krp},
                              {"ckv_pages": cn_l, "krope_pages": kn_l},
                              lyr, bt, pos_b, cap, val, tok=0)
            cp, krp = got["ckv_pages"], got["krope_pages"]
            ckv = jax.lax.all_gather(gather_pages(cp[lyr], bt), shard.axis,
                                     axis=2, tiled=True)
            kr = jax.lax.all_gather(gather_pages(krp[lyr], bt), shard.axis,
                                    axis=2, tiled=True)
            if csp is not None:
                # scale pools are replicated [L, P, ps]; the all-gathered
                # views are rank-complete, so dequant matches unsharded
                ckv = dequantize_kv(ckv, gather_pages(csp[lyr], bt),
                                    jnp.float32)
                kr = dequantize_kv(kr, gather_pages(krsp[lyr], bt),
                                   jnp.float32)
            d = jax.lax.axis_index(shard.axis)
            pm, pl_, pnv = mla_decode_partials(
                qc, ckv, kr, kvl, start_page=d * sp, n_splits=sp,
                page_size=page_size, scale=scale, softcap=cfg.attn_softcap)
            pm = jax.lax.all_gather(pm, shard.axis, axis=1, tiled=True)
            pl_ = jax.lax.all_gather(pl_, shard.axis, axis=1, tiled=True)
            pnv = jax.lax.all_gather(pnv, shard.axis, axis=1, tiled=True)
            return mla_combine_partials(pm, pl_, pnv, qc.dtype), cp, krp

        pspec = shard.spec(4, -1)
        nspec = shard.spec(3, -1)
        rep = shard.replicated
        specs = [pspec, pspec, nspec, nspec, rep, rep, rep, rep, rep, rep]
        operands = [cache["ckv_pages"], cache["krope_pages"], ckv_q, kr_q,
                    q_cat, bt_rows, pos, valid, kv_len,
                    jnp.asarray(layer, jnp.int32)]
        if scale_new:
            specs += [rep, rep]
            operands += [scale_new["ckv_scale"], scale_new["krope_scale"]]
        out_lat, ckv_pages, krope_pages = jax.shard_map(
            local, mesh=shard.mesh, check_vma=False,
            in_specs=tuple(specs),
            out_specs=(rep, pspec, pspec),
        )(*operands)
    else:
        got = _write_rows(cache, {"ckv_pages": ckv_q, "krope_pages": kr_q},
                          layer, bt_rows, pos, cap, valid, tok=0)
        ckv_pages, krope_pages = got["ckv_pages"], got["krope_pages"]
        out_lat = fusemax_mla_decode_paged(
            q_cat, ckv_pages, krope_pages, bt_rows, kv_len, layer=layer,
            scale=scale, softcap=cfg.attn_softcap,
            ckv_scale=scale_new.get("ckv_scale"),
            krope_scale=scale_new.get("krope_scale"),
            impl=rt.attn_impl,
            exp_impl=rt.exp_impl,
            interpret=rt.interpret,
        )                                                # [B,H,1,r]
    out = jnp.einsum("bhsr,rhe->bhse", out_lat, p["w_uv"].astype(dt))
    y = jnp.einsum("bhse,hed->bsd", out, p["wo"].astype(dt))
    return y, {"ckv_pages": ckv_pages, "krope_pages": krope_pages,
               **scale_new}


def mla_verify_paged(
    p, x: jnp.ndarray, cache: dict, bt_rows: jnp.ndarray,
    kv_len: jnp.ndarray, span: jnp.ndarray,
    cfg: ModelConfig, spec: LayerSpec, rt: Runtime, layer,
) -> tuple[jnp.ndarray, dict]:
    """Paged latent-space verify: the P-chain analogue of
    :func:`mla_decode_paged`, as layer ``layer`` of the stacked pools
    (chain contract in :func:`gqa_verify`;
    chain latents land in the slot's scratch draft pages through the
    block table).  Unsharded only — the engine gates speculation off
    under a device mesh."""
    m = cfg.mla
    b, pq, _ = x.shape
    dt = x.dtype
    pos = (kv_len - 1)[:, None] + jnp.arange(pq)[None]   # [B, P]
    q_nope, q_rope, ckv_new, krope_new = _mla_qkv_latent(p, x, cfg, pos)
    page_size = cache["ckv_pages"].shape[2]
    cap = bt_rows.shape[1] * page_size
    valid = (jnp.arange(pq)[None] < span[:, None]) & (kv_len > 0)[:, None]
    scale = 1.0 / math.sqrt(m.nope_dim + m.rope_dim)

    q_eff = jnp.einsum("bhse,rhe->bhsr", q_nope, p["w_uk"].astype(dt))
    q_cat = jnp.concatenate([q_eff, q_rope], axis=-1)    # [B,H,P,r+rd]

    ckv_q, ckv_s, kr_q, kr_s = _mla_quant_new(cache, ckv_new, krope_new)
    new = {"ckv_pages": ckv_q, "krope_pages": kr_q}
    if ckv_s is not None:
        new.update(ckv_scale=ckv_s, krope_scale=kr_s)
    pools = _write_rows(cache, new, layer, bt_rows, pos, cap, valid, tok=0)
    out_lat = fusemax_mla_decode_paged(
        q_cat, pools["ckv_pages"], pools["krope_pages"], bt_rows, kv_len,
        layer=layer,
        scale=scale, softcap=cfg.attn_softcap,
        ckv_scale=pools.get("ckv_scale"),
        krope_scale=pools.get("krope_scale"),
        impl=rt.attn_impl,
        exp_impl=rt.exp_impl,
        interpret=rt.interpret,
    )                                                    # [B,H,P,r]
    out = jnp.einsum("bhsr,rhe->bhse", out_lat, p["w_uv"].astype(dt))
    y = jnp.einsum("bhse,hed->bsd", out, p["wo"].astype(dt))
    return y, pools
