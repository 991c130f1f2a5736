"""Public attention ops: jit'd wrappers around the FuseMax kernels.

Entry points
------------
``fusemax_attention`` — [B, Hq, P, E] × [B, Hkv, M, E/F] → [B, Hq, P, F].
  impl="pallas"  the Pallas TPU kernel (interpret=True on CPU),
  impl="jnp"     a differentiable custom-VJP 1-pass implementation (the
                 numeric Cascade 5 with FlashAttention-2-style recompute
                 backward) — the training / dry-run path,
  impl="ref"     the 3-pass oracle (testing),
  impl="auto"    pallas on TPU, jnp elsewhere.

``fusemax_decode`` — one-token queries against (ragged) KV caches with the
  split-K instantiation of the cascade.

All GQA head folding, block padding, and dtype promotion happen here so
the kernels only ever see aligned shapes.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import autotune, ref as _ref
from repro.kernels.decode import (
    decode_paged_cascade, decode_splitk_cascade,
    fusemax_decode_paged_pallas, fusemax_decode_pallas,
    fusemax_mla_decode_paged_pallas, mla_decode_paged_cascade,
    mla_verify_chain_cascade, verify_chain_cascade,
)
from repro.kernels.fusemax import (
    NEG_INF, fusemax_attention_pallas, prefill_cascade,
)

# Every public attention op dispatches to exactly one declared cascade
# (co-located with its kernel family).  repro.analysis.report --check
# verifies the declarations symbolically (pass counts, footprints) and
# repro.analysis.lint structurally (grid sweeps, accumulator shapes) —
# new kernels must register here before they can land (ROADMAP rule).
KERNEL_CASCADES = {
    "mha_reference": _ref.reference_cascade,
    "decode_reference": _ref.reference_cascade,
    "fusemax_attention": prefill_cascade,
    "fusemax_decode": decode_splitk_cascade,
    "fusemax_decode_paged": decode_paged_cascade,
    "fusemax_mla_decode_paged": mla_decode_paged_cascade,
    "fusemax_decode[p>1]": verify_chain_cascade,
    "fusemax_decode_paged[p>1]": verify_chain_cascade,
    "fusemax_mla_decode_paged[p>1]": mla_verify_chain_cascade,
}


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Differentiable 1-pass attention in jnp (custom VJP, FA-2-style backward)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _make_flash_jnp(
    causal: bool,
    window: Optional[int],
    softcap: Optional[float],
    scale: float,
    q_offset: int,
    block: int,
    unroll: bool = False,
):
    """Build a custom-VJP flash attention over [B, Hkv, G, P, E] queries.

    Forward: Cascade 5 via lax.scan over M1 blocks, carrying (RM, RD, RNV);
    saves only (out, LSE) — O(P) residuals per fiber, independent of M.
    Backward: one more pass over M blocks recomputing SLN from (Q, K, LSE),
    the standard recompute backward that the 1-pass cascade enables.
    """

    def _mask(p: int, m_lo, m_len: int, dtype):
        if not causal and window is None:
            return None
        qpos = jnp.arange(p)[:, None] + q_offset
        kpos = m_lo + jnp.arange(m_len)[None, :]
        ok = jnp.ones((p, m_len), dtype=bool)
        if causal:
            ok = ok & (kpos <= qpos)
        if window is not None:
            ok = ok & (kpos > qpos - window)
        return jnp.where(ok, jnp.array(0.0, dtype), jnp.array(NEG_INF, dtype))

    def _logits(q, k_blk, m_lo, m_len):
        s = jnp.einsum("bhgpe,bhme->bhgpm", q, k_blk) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        msk = _mask(q.shape[-2], m_lo, m_len, s.dtype)
        if msk is not None:
            s = s + msk
        return s

    def fwd(q, k, v):
        *bh, p, e = q.shape
        m = k.shape[-2]
        f = v.shape[-1]
        n_blk = m // block
        qf = q.astype(jnp.float32)
        kb = jnp.moveaxis(
            k.astype(jnp.float32).reshape(*k.shape[:-2], n_blk, block, e),
            -3, 0)
        vb = jnp.moveaxis(
            v.astype(jnp.float32).reshape(*v.shape[:-2], n_blk, block, f),
            -3, 0)
        batch = q.shape[:-2]
        rm0 = jnp.full((*batch, p), NEG_INF, jnp.float32)
        rd0 = jnp.zeros((*batch, p), jnp.float32)
        rnv0 = jnp.zeros((*batch, p, f), jnp.float32)

        def step(carry, xs):
            rm, rd, rnv = carry
            i, k_i, v_i = xs
            s = _logits(qf, k_i, i * block, block)          # Eq. 42
            lm = jnp.max(s, axis=-1)                        # Eq. 43
            rm_new = jnp.maximum(rm, lm)                    # Eq. 44
            p_ = jnp.exp(s - rm_new[..., None])             # Eq. 45
            sld = jnp.sum(p_, axis=-1)                      # Eq. 46
            slnv = jnp.einsum("bhgpm,bhmf->bhgpf", p_, v_i) # Eq. 47
            prm = jnp.exp(rm - rm_new)                      # Eq. 48
            rd_new = rd * prm + sld                         # Eqs. 49-50
            rnv_new = rnv * prm[..., None] + slnv           # Eqs. 51-52
            return (rm_new, rd_new, rnv_new), None

        idx = jnp.arange(n_blk)
        (rm, rd, rnv), _ = jax.lax.scan(
            step, (rm0, rd0, rnv0), (idx, kb, vb),
            unroll=n_blk if unroll else 1)
        rd_safe = jnp.where(rd == 0.0, 1.0, rd)
        out = (rnv / rd_safe[..., None]).astype(q.dtype)    # Eq. 53
        lse = rm + jnp.log(rd_safe)                         # logsumexp
        return out, lse

    def value(q, k, v):
        return fwd(q, k, v)[0]

    def fwd_vjp(q, k, v):
        out, lse = fwd(q, k, v)
        return out, (q, k, v, out, lse)

    def bwd_vjp(res, dout):
        q, k, v, out, lse = res
        *_, p, e = q.shape
        m = k.shape[-2]
        f = v.shape[-1]
        n_blk = m // block
        qf = q.astype(jnp.float32)
        do = dout.astype(jnp.float32)
        # D_p = Σ_f dO ∘ O  (rowsum)
        delta = jnp.sum(do * out.astype(jnp.float32), axis=-1)  # [...,P]

        kb = jnp.moveaxis(
            k.astype(jnp.float32).reshape(*k.shape[:-2], n_blk, block, e),
            -3, 0)
        vb = jnp.moveaxis(
            v.astype(jnp.float32).reshape(*v.shape[:-2], n_blk, block, f),
            -3, 0)

        def step(dq, xs):
            i, k_i, v_i = xs
            s_raw = jnp.einsum("bhgpe,bhme->bhgpm", qf, k_i) * scale
            if softcap is not None:
                t = jnp.tanh(s_raw / softcap)
                s_c = softcap * t
            else:
                s_c = s_raw
            msk = _mask(p, i * block, block, s_c.dtype)
            if msk is not None:
                s_c = s_c + msk
            p_ = jnp.exp(s_c - lse[..., None])              # = A (recompute)
            dv_i = jnp.einsum("bhgpm,bhgpf->bhmf", p_, do)
            dp = jnp.einsum("bhgpf,bhmf->bhgpm", do, v_i)
            ds = p_ * (dp - delta[..., None])
            if softcap is not None:
                ds = ds * (1.0 - t * t)                     # d softcap
            dq = dq + jnp.einsum("bhgpm,bhme->bhgpe", ds, k_i) * scale
            dk_i = jnp.einsum("bhgpm,bhgpe->bhme", ds, qf) * scale
            return dq, (dk_i, dv_i)

        idx = jnp.arange(n_blk)
        dq0 = jnp.zeros_like(qf)
        dq, (dk_b, dv_b) = jax.lax.scan(step, dq0, (idx, kb, vb),
                                        unroll=n_blk if unroll else 1)
        dk = jnp.moveaxis(dk_b, 0, -3).reshape(k.shape)
        dv = jnp.moveaxis(dv_b, 0, -3).reshape(v.shape)
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)

    flash = jax.custom_vjp(value)
    flash.defvjp(fwd_vjp, bwd_vjp)
    return flash


def _banded_window_jnp(q5, k, v, window, softcap, scale, block_k,
                       unroll=False):
    """Sliding-window attention as per-chunk bands.

    Queries are split into S/W chunks; chunk c ≥ 1 attends only the 2W-key
    band [(c-1)W, (c+1)W) (fold chunks into the batch dim and reuse the
    1-pass flash with q_offset=W — the causal+window mask inside the band
    is chunk-independent); chunk 0 attends its own W keys.  Exact, and
    drops sliding-window score work from O(S²) to O(S·2W).
    """
    b, h, g, s, e = q5.shape
    f = v.shape[-1]
    w = window
    nc = s // w

    # chunk 0: plain causal(+window) over its own keys
    flash0 = _make_flash_jnp(True, w, softcap, scale, 0, min(block_k, w),
                             unroll)
    out0 = flash0(q5[:, :, :, :w], k[:, :, :w], v[:, :, :w])

    # chunks 1..nc-1: uniform band geometry, folded into batch
    kc = k.reshape(b, h, nc, w, e)
    vc = v.reshape(b, h, nc, w, f)
    band_k = jnp.concatenate([kc[:, :, :-1], kc[:, :, 1:]], axis=3)
    band_v = jnp.concatenate([vc[:, :, :-1], vc[:, :, 1:]], axis=3)
    qc = q5.reshape(b, h, g, nc, w, e)[:, :, :, 1:]          # [b,h,g,nc-1,w,e]

    fold = nc - 1
    qb = (qc.transpose(0, 3, 1, 2, 4, 5)
          .reshape(b * fold, h, g, w, e))
    kb = (band_k.transpose(0, 2, 1, 3, 4)
          .reshape(b * fold, h, 2 * w, e))
    vb = (band_v.transpose(0, 2, 1, 3, 4)
          .reshape(b * fold, h, 2 * w, f))
    flash = _make_flash_jnp(True, w, softcap, scale, w,
                            min(block_k, 2 * w), unroll)
    ob = flash(qb, kb, vb)                                   # [b·nc-1,h,g,w,f]
    ob = (ob.reshape(b, fold, h, g, w, f)
          .transpose(0, 2, 3, 1, 4, 5)
          .reshape(b, h, g, (nc - 1) * w, f))
    return jnp.concatenate([out0, ob], axis=3)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def fusemax_attention(
    q: jnp.ndarray,   # [B, Hq, P, E]
    k: jnp.ndarray,   # [B, Hkv, M, E]
    v: jnp.ndarray,   # [B, Hkv, M, F]
    *,
    causal: bool = False,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    q_offset: int = 0,
    impl: str = "auto",
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    exp_impl: str = "native",
    interpret: Optional[bool] = None,
    unroll_scan: bool = False,
) -> jnp.ndarray:
    """FuseMax attention (1-pass cascade, deferred division).

    ``block_q`` / ``block_k`` left as ``None`` are resolved by the
    autotuner (:mod:`repro.kernels.autotune`) per (shape, backend).
    """
    b, hq, p, e = q.shape
    _, hkv, m, f = v.shape
    if hq % hkv:
        raise ValueError(f"Hq={hq} not a multiple of Hkv={hkv}")
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / (e ** 0.5)

    if impl == "auto":
        impl = "pallas" if _on_tpu() else "jnp"

    if block_q is None or block_k is None:
        tuned = autotune.attention_params(
            p * group, m, e, f, backend=jax.default_backend(), impl=impl)
        block_q = tuned.block_q if block_q is None else block_q
        block_k = tuned.block_k if block_k is None else block_k

    if impl == "ref":
        return _ref.mha_reference(
            q, k, v, causal=causal, window=window, softcap=softcap,
            scale=scale, q_offset=q_offset)

    if impl == "jnp":
        # fold heads: [B, Hkv, G, P, E]
        q5 = q.reshape(b, hkv, group, p, e)
        if (window is not None and causal and q_offset == 0 and p == m
                and m % window == 0 and m // window >= 2
                and os.environ.get("REPRO_NO_BANDING") != "1"):
            # banded evaluation for sliding-window layers: each W-chunk of
            # queries touches only its 2W-key band ⇒ score work S·2W
            # instead of S² (§Perf lever; exact — masks unchanged)
            out = _banded_window_jnp(q5, k, v, window, softcap, scale,
                                     block_k, unroll_scan)
            return out.reshape(b, hq, p, f)
        mb = min(block_k, m)
        if m % mb:
            mb = m  # irregular tail: single block
        flash = _make_flash_jnp(causal, window, softcap, scale, q_offset, mb,
                                unroll_scan)
        out = flash(q5, k, v)
        return out.reshape(b, hq, p, f)

    if impl != "pallas":
        raise ValueError(f"unknown impl: {impl}")

    interpret = (not _on_tpu()) if interpret is None else interpret
    # fold GQA groups into query rows: row r = p·group + g → qpos = r//group
    q_f = (
        q.reshape(b, hkv, group, p, e)
        .transpose(0, 1, 3, 2, 4)
        .reshape(b * hkv, p * group, e)
    )
    k_f = k.reshape(b * hkv, m, e)
    v_f = v.reshape(b * hkv, m, f)

    pg = p * group
    block_q = min(block_q, _round_up(pg, 8))
    block_k_eff = min(block_k, _round_up(m, 128))
    pg_pad = _round_up(pg, block_q)
    m_pad = _round_up(m, block_k_eff)
    if pg_pad != pg:
        q_f = jnp.pad(q_f, ((0, 0), (0, pg_pad - pg), (0, 0)))
    if m_pad != m:
        k_f = jnp.pad(k_f, ((0, 0), (0, m_pad - m), (0, 0)))
        v_f = jnp.pad(v_f, ((0, 0), (0, m_pad - m), (0, 0)))

    out = fusemax_attention_pallas(
        q_f, k_f, v_f,
        scale=scale, causal=causal, window=window, softcap=softcap,
        q_offset=q_offset, group=group,
        block_q=block_q, block_k=block_k_eff,
        m_valid=m, exp_impl=exp_impl, interpret=interpret,
    )
    out = out[:, :pg]
    return (
        out.reshape(b, hkv, p, group, f)
        .transpose(0, 1, 3, 2, 4)
        .reshape(b, hq, p, f)
    )


def _decode_splitk_jnp(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    kv_len: jnp.ndarray,
    *, scale: float, softcap: Optional[float], window: Optional[int],
    splits: int,
) -> jnp.ndarray:
    """jnp split-K decode over ragged caches (mirrors the Pallas kernel)."""
    b, hq, p, e = q.shape
    _, hkv, m, f = v.shape
    group = hq // hkv
    ms = m // splits
    q5 = q.astype(jnp.float32).reshape(b, hkv, group, e)   # P == 1 squeezed
    ks = k.astype(jnp.float32).reshape(b, hkv, splits, ms, e)
    vs = v.astype(jnp.float32).reshape(b, hkv, splits, ms, f)

    logits = jnp.einsum("bhge,bhsme->bhsgm", q5, ks) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    kpos = (jnp.arange(splits)[:, None] * ms + jnp.arange(ms)[None, :])
    ok = kpos[None] < kv_len[:, None, None]                # [B, S, Ms]
    if window is not None:
        qpos = kv_len[:, None, None] - 1
        ok &= kpos[None] > qpos - window
    logits = jnp.where(ok[:, None, :, None, :], logits, NEG_INF)

    lm = jnp.max(logits, axis=-1)                          # [b,h,s,g]
    sln = jnp.exp(logits - lm[..., None])
    sld = jnp.sum(sln, axis=-1)
    slnv = jnp.einsum("bhsgm,bhsmf->bhsgf", sln, vs)
    gm = jnp.max(lm, axis=2, keepdims=True)
    cf = jnp.exp(lm - gm)
    rd = jnp.sum(sld * cf, axis=2)                         # [b,h,g]
    rnv = jnp.sum(slnv * cf[..., None], axis=2)
    rd = jnp.where(rd == 0.0, 1.0, rd)
    out = rnv / rd[..., None]
    return out.reshape(b, hq, 1, f).astype(q.dtype)


def _verify_splitk_jnp(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    kv_len: jnp.ndarray,
    *, scale: float, softcap: Optional[float], splits: int,
) -> jnp.ndarray:
    """Multi-query (draft-chain verify) split-K decode.

    Query position ``j`` of the P-token chain sits at logical position
    ``kv_len - 1 + j`` and attends to keys ``< kv_len + j`` (``kv_len``
    counts the cache *including query 0*) — the causal intra-draft mask.
    Mirrors :func:`_decode_splitk_jnp` exactly — same split geometry
    (resolved from the same autotune key, which never sees P), same
    einsum contractions with P as a free batch axis, same reduction
    order — so each position's output matches the single-token path
    bit-for-bit and committed speculative streams are identical to
    non-speculative decode.
    """
    b, hq, p, e = q.shape
    _, hkv, m, f = v.shape
    group = hq // hkv
    ms = m // splits
    q6 = q.astype(jnp.float32).reshape(b, hkv, group, p, e)
    ks = k.astype(jnp.float32).reshape(b, hkv, splits, ms, e)
    vs = v.astype(jnp.float32).reshape(b, hkv, splits, ms, f)

    logits = jnp.einsum("bhgpe,bhsme->bhsgpm", q6, ks) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    kpos = (jnp.arange(splits)[:, None] * ms + jnp.arange(ms)[None, :])
    lim = kv_len[:, None] + jnp.arange(p)[None, :]           # [B, P]
    ok = kpos[None, None] < lim[:, :, None, None]            # [B, P, S, Ms]
    ok = ok.transpose(0, 2, 1, 3)                            # [B, S, P, Ms]
    logits = jnp.where(ok[:, None, :, None], logits, NEG_INF)

    lm = jnp.max(logits, axis=-1)                            # [b,h,s,g,p]
    sln = jnp.exp(logits - lm[..., None])
    sld = jnp.sum(sln, axis=-1)
    slnv = jnp.einsum("bhsgpm,bhsmf->bhsgpf", sln, vs)
    gm = jnp.max(lm, axis=2, keepdims=True)
    cf = jnp.exp(lm - gm)
    rd = jnp.sum(sld * cf, axis=2)                           # [b,h,g,p]
    rnv = jnp.sum(slnv * cf[..., None], axis=2)
    rd = jnp.where(rd == 0.0, 1.0, rd)
    out = rnv / rd[..., None]
    return out.reshape(b, hq, p, f).astype(q.dtype)


def _fold_decode_q(q: jnp.ndarray, b: int, hkv: int, group: int,
                   e: int) -> jnp.ndarray:
    """Fold GQA groups into kernel query rows ([B, Hq, P, E] →
    [B·Hkv, P·G_pad, E], G padded to the 8-sublane floor; P = 1 for
    plain decode, = the chain length for verify — row ``r`` carries
    draft position ``r // G_pad``) — shared by the dense and paged
    decode dispatch paths."""
    p = q.shape[2]
    g_pad = max(8, _round_up(group, 8))
    q_f = q.reshape(b, hkv, group, p, e).transpose(0, 1, 3, 2, 4)
    if g_pad != group:
        q_f = jnp.pad(q_f, ((0, 0), (0, 0), (0, 0),
                            (0, g_pad - group), (0, 0)))
    return q_f.reshape(b * hkv, p * g_pad, e)


def _unfold_decode_out(out: jnp.ndarray, b: int, hkv: int, group: int,
                       f: int, p: int = 1) -> jnp.ndarray:
    """Inverse of :func:`_fold_decode_q` for kernel outputs
    ([B·Hkv, P·G_pad, F] → [B, Hq, P, F])."""
    g_pad = out.shape[1] // p
    out = out.reshape(b, hkv, p, g_pad, f)[:, :, :, :group]
    return out.transpose(0, 1, 3, 2, 4).reshape(b, hkv * group, p, f)


def gather_pages(pages: jnp.ndarray, block_table: jnp.ndarray) -> jnp.ndarray:
    """Materialize a block-table view of a page pool.

    pages: [P, page_size, *tail]; block_table: [B, W] int32 →
    [B, W·page_size, *tail].  Unallocated table entries hold the
    out-of-range sentinel id (``P``) — the gather clamps them to the last
    page and callers mask by the logical length, so the garbage never
    contributes (while the matching *scatter* drops sentinel writes
    outright).  This is the jnp/ref read path; the Pallas kernel resolves
    pages inside its ``index_map`` instead and never materializes this
    view.
    """
    b = block_table.shape[0]
    bt = jnp.minimum(block_table, pages.shape[0] - 1)
    g = pages[bt]                               # [B, W, page_size, *tail]
    return g.reshape(b, -1, *pages.shape[2:])


def gather_kv_pages(pages: jnp.ndarray,
                    block_table: jnp.ndarray) -> jnp.ndarray:
    """:func:`gather_pages` for a head-major GQA pool: pages
    [P, Hkv, page_size, *tail] → [B, Hkv, W·page_size, *tail], the dense
    cache's layout (same sentinel clamp)."""
    b, w = block_table.shape
    bt = jnp.minimum(block_table, pages.shape[0] - 1)
    g = jnp.moveaxis(pages[bt], 2, 1)           # [B, Hkv, W, ps, *tail]
    return g.reshape(b, pages.shape[1], w * pages.shape[2],
                     *pages.shape[3:])


def _scale_block_k(block_k: int, page_size: int, quantized: bool) -> int:
    """Quantized pools tile their scale pools with ``block_k`` on the lane
    axis, which the TPU accepts only as the whole page or a multiple of
    128: otherwise sweep one whole page per grid step."""
    if quantized and block_k != page_size and block_k % 128:
        return page_size
    return block_k


def _lift_layer(*pools):
    """A one-layer pool is the ``L = 1`` case of a pool stacked over
    layers: ``pool[None]``, read at layer 0 (``None`` passes through)."""
    return [None if a is None else a[None] for a in pools]


def fusemax_decode_paged(
    q: jnp.ndarray,            # [B, Hq, 1, E]
    k_pages: jnp.ndarray,      # [L, P, Hkv, page_size, E] or [P, ...]
    v_pages: jnp.ndarray,      # [L, P, Hkv, page_size, F] or [P, ...]
    block_table: jnp.ndarray,  # [B, W] int32 page ids
    kv_len: jnp.ndarray,       # [B] valid logical lengths
    *,
    layer=0,
    capacity: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    impl: str = "auto",
    splits: Optional[int] = None,
    block_k: Optional[int] = None,
    exp_impl: str = "native",
    interpret: Optional[bool] = None,
    k_scale: Optional[jnp.ndarray] = None,   # [L, P, Hkv, page_size]
    v_scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Single-token decode against a *paged* KV cache.

    The pools are stacked over a run's layers and ``layer`` (an int or a
    traced int32 scalar, e.g. a layer loop's counter) selects the one to
    read; a four-dimensional pool is a single layer (``L = 1``, layer 0).
    The Pallas kernel reads the stacked pool in place through its
    ``index_map``; the jnp/ref paths gather from ``pool[layer]``, the same
    arithmetic.

    ``capacity`` truncates the logical view to that many tokens (ring
    caches: capacity = window, which may not fill the last page) — with it,
    the jnp path sees exactly the dense cache's [B, Hkv, capacity, *] view,
    so outputs are bit-identical to :func:`fusemax_decode` over the dense
    layout.  The Pallas path runs the true paged kernel (block-table lookup
    in the index_map, page-aligned splits from the autotuner).

    ``k_scale``/``v_scale`` mark the pools as quantized (fp8/int8 codes
    with per-token-per-head scales): the jnp/ref paths dequantize the
    gathered view before delegating, the Pallas path streams the scale
    tiles into the kernel, which applies them to the score and
    probability columns.  Scale pools follow the same sentinel/clamp
    discipline as the data pools, so masking by ``kv_len`` is unchanged.

    Shard contract (device-sharded pools): every computation here is
    independent per (batch, kv-head) fiber and the autotuned
    ``splits``/``block_k`` depend only on the page geometry and the
    head-group ratio — both invariant under kv-head sharding — so the
    attention layer may call this on a kv-head *shard* of
    (q, k_pages, v_pages) under ``shard_map`` (the block table is
    replicated; page ids are global) and get results bit-identical to
    the corresponding head slice of the full-pool call.
    """
    if v_pages.ndim == 4:
        k_pages, v_pages, k_scale, v_scale = _lift_layer(
            k_pages, v_pages, k_scale, v_scale)
    b, hq, p, e = q.shape
    hkv, page_size, f = v_pages.shape[2:]
    w = block_table.shape[1]
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / (e ** 0.5)

    if impl == "auto":
        impl = "pallas" if _on_tpu() else "jnp"

    if impl in ("jnp", "ref"):
        # gather through the table, then delegate: same shapes, same
        # autotuned splits, same arithmetic as the dense layout (P > 1 —
        # the speculative verify chain — rides the same delegation)
        cap = w * page_size if capacity is None else capacity
        k = gather_kv_pages(k_pages[layer], block_table)
        v = gather_kv_pages(v_pages[layer], block_table)
        if k_scale is not None:
            ks = gather_kv_pages(k_scale[layer], block_table)
            vs = gather_kv_pages(v_scale[layer], block_table)
            k = k.astype(jnp.float32) * ks.astype(jnp.float32)[..., None]
            v = v.astype(jnp.float32) * vs.astype(jnp.float32)[..., None]
        return fusemax_decode(
            q, k[:, :, :cap], v[:, :, :cap], kv_len,
            softcap=softcap, scale=scale, impl=impl, splits=splits,
            block_k=block_k, exp_impl=exp_impl, interpret=interpret)

    if impl != "pallas":
        raise ValueError(f"unknown impl: {impl}")

    if splits is None or block_k is None:
        tuned = autotune.paged_decode_params(
            w, page_size, max(group, 8), e, f,
            backend=jax.default_backend(), impl=impl,
            elem_bytes=jnp.dtype(k_pages.dtype).itemsize)
        splits = tuned.splits if splits is None else splits
        block_k = tuned.block_k if block_k is None else block_k
    splits = max(1, min(splits, w))
    while w % splits:
        splits -= 1
    block_k = min(block_k, page_size)
    while page_size % block_k:
        block_k -= 1
    block_k = autotune.verify_block_k(
        block_k, p=p, g=max(group, 8), e=e, f=f)
    block_k = _scale_block_k(block_k, page_size, k_scale is not None)

    interpret = (not _on_tpu()) if interpret is None else interpret
    out = fusemax_decode_paged_pallas(
        _fold_decode_q(q, b, hkv, group, e), k_pages, v_pages,
        block_table, kv_len, layer,
        scale=scale, softcap=softcap, hkv=hkv, splits=splits,
        block_k=block_k, exp_impl=exp_impl, interpret=interpret, p=p,
        k_scale=k_scale, v_scale=v_scale,
    )
    return _unfold_decode_out(out, b, hkv, group, f, p=p)


def mla_decode_partials(
    q_cat: jnp.ndarray,     # [B, H, 1, rank + rope_dim] absorbed + rope q
    ckv: jnp.ndarray,       # [B, T, rank] latent history (gathered view)
    krope: jnp.ndarray,     # [B, T, rope_dim] positional-key history
    kv_len: jnp.ndarray,    # [B] valid logical lengths
    *,
    start_page,             # int or traced int32: first page of this sweep
    n_splits: int,
    page_size: int,
    scale: float,
    softcap: Optional[float] = None,
):
    """Per-page split-K partials of the absorbed-form MLA decode cascade.

    One split per block-table page: split ``j`` covers logical tokens
    ``[(start_page+j)·ps, (start_page+j+1)·ps)`` and yields the local
    running state (RM, RD, RNV) of Eqs. 48-52 — ``([B, n, H], [B, n, H],
    [B, n, H, rank])``.  Every split is an identically-shaped pair of
    GEMMs, so a rank-sharded pool can hand each device a contiguous
    ``start_page`` strip (``start_page`` may be a traced
    ``axis_index``-derived offset), all-gather the page-ordered stacks,
    and recover the single-device result bit-for-bit in
    :func:`mla_combine_partials`.

    An all-masked (dead) split degrades exactly like the dense split-K
    path: RM = -inf, RD = page_size — its combine weight exp(-inf - gm)
    is zero, so it never contributes.
    """
    q3 = q_cat[:, :, 0].astype(jnp.float32)                 # [B, H, r+rd]
    k3 = jnp.concatenate([ckv, krope], axis=-1).astype(jnp.float32)
    v3 = ckv.astype(jnp.float32)
    pms, pls, pnvs = [], [], []
    for j in range(n_splits):
        lo = (start_page + j) * page_size
        kt = jax.lax.dynamic_slice_in_dim(k3, lo, page_size, axis=1)
        vt = jax.lax.dynamic_slice_in_dim(v3, lo, page_size, axis=1)
        logits = jnp.einsum("bhe,bme->bhm", q3, kt) * scale
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        kpos = lo + jnp.arange(page_size)[None, None]
        ok = kpos < kv_len[:, None, None]
        logits = jnp.where(ok, logits, NEG_INF)
        lm = jnp.max(logits, axis=-1)                       # [B, H]
        sln = jnp.exp(logits - lm[..., None])
        pms.append(lm)
        pls.append(jnp.sum(sln, axis=-1))
        pnvs.append(jnp.einsum("bhm,bmf->bhf", sln, vt))
    return jnp.stack(pms, 1), jnp.stack(pls, 1), jnp.stack(pnvs, 1)


def mla_combine_partials(pm, pl_, pnv, dtype) -> jnp.ndarray:
    """Combine :func:`mla_decode_partials` stacks (associative running-max
    algebra, Eqs. 48-52) → the latent decode output [B, H, 1, rank]."""
    gm = jnp.max(pm, axis=1, keepdims=True)
    cf = jnp.exp(pm - gm)                                   # [B, S, H]
    rd = jnp.sum(pl_ * cf, axis=1)                          # [B, H]
    rnv = jnp.sum(pnv * cf[..., None], axis=1)              # [B, H, rank]
    rd = jnp.where(rd == 0.0, 1.0, rd)
    return (rnv / rd[..., None])[:, :, None].astype(dtype)


def mla_verify_partials(
    q_cat: jnp.ndarray,     # [B, H, P, rank + rope_dim] absorbed + rope q
    ckv: jnp.ndarray,       # [B, T, rank] latent history (gathered view)
    krope: jnp.ndarray,     # [B, T, rope_dim] positional-key history
    kv_len: jnp.ndarray,    # [B] lengths *including draft position 0*
    *,
    start_page,
    n_splits: int,
    page_size: int,
    scale: float,
    softcap: Optional[float] = None,
):
    """Multi-query (draft-chain verify) variant of
    :func:`mla_decode_partials`: chain position ``j`` attends to latents
    ``< kv_len + j``.  Same per-page split structure and reduction order
    with P as a free batch axis, so each position matches the P = 1 path
    bit-for-bit.  Returns ([B, n, H, P], [B, n, H, P], [B, n, H, P, r])."""
    p = q_cat.shape[2]
    qp = q_cat.astype(jnp.float32)                          # [B, H, P, e]
    k3 = jnp.concatenate([ckv, krope], axis=-1).astype(jnp.float32)
    v3 = ckv.astype(jnp.float32)
    lim = kv_len[:, None] + jnp.arange(p)[None, :]          # [B, P]
    pms, pls, pnvs = [], [], []
    for j in range(n_splits):
        lo = (start_page + j) * page_size
        kt = jax.lax.dynamic_slice_in_dim(k3, lo, page_size, axis=1)
        vt = jax.lax.dynamic_slice_in_dim(v3, lo, page_size, axis=1)
        logits = jnp.einsum("bhpe,bme->bhpm", qp, kt) * scale
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        kpos = lo + jnp.arange(page_size)
        ok = kpos[None, None] < lim[:, :, None]             # [B, P, ps]
        logits = jnp.where(ok[:, None], logits, NEG_INF)
        lm = jnp.max(logits, axis=-1)                       # [B, H, P]
        sln = jnp.exp(logits - lm[..., None])
        pms.append(lm)
        pls.append(jnp.sum(sln, axis=-1))
        pnvs.append(jnp.einsum("bhpm,bmf->bhpf", sln, vt))
    return jnp.stack(pms, 1), jnp.stack(pls, 1), jnp.stack(pnvs, 1)


def mla_verify_combine(pm, pl_, pnv, dtype) -> jnp.ndarray:
    """Combine :func:`mla_verify_partials` stacks → [B, H, P, rank]."""
    gm = jnp.max(pm, axis=1, keepdims=True)
    cf = jnp.exp(pm - gm)                                   # [B, S, H, P]
    rd = jnp.sum(pl_ * cf, axis=1)                          # [B, H, P]
    rnv = jnp.sum(pnv * cf[..., None], axis=1)              # [B, H, P, r]
    rd = jnp.where(rd == 0.0, 1.0, rd)
    return (rnv / rd[..., None]).astype(dtype)


def fusemax_mla_decode_paged(
    q: jnp.ndarray,             # [B, H, 1, rank + rope_dim] absorbed q_cat
    ckv_pages: jnp.ndarray,     # [L, P, page_size, rank] or [P, ...]
    krope_pages: jnp.ndarray,   # [L, P, page_size, rope_dim] or [P, ...]
    block_table: jnp.ndarray,   # [B, W] int32 page ids
    kv_len: jnp.ndarray,        # [B] valid logical lengths
    *,
    layer=0,
    scale: Optional[float] = None,
    softcap: Optional[float] = None,
    impl: str = "auto",
    splits: Optional[int] = None,
    block_k: Optional[int] = None,
    exp_impl: str = "native",
    interpret: Optional[bool] = None,
    ckv_scale: Optional[jnp.ndarray] = None,   # [L, P, page_size]
    krope_scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Single-token MLA decode against a paged *latent* cache, read at
    ``layer`` of pools stacked over layers (a three-dimensional pool is a
    single layer) — the contract of :func:`fusemax_decode_paged`.

    Queries arrive W_uk-absorbed (``q_eff = q_nopeᵀW_uk`` concatenated
    with ``q_rope``); the result is the latent output [B, H, 1, rank],
    still to be lifted through W_uv by the caller — per-head K/V never
    exists on either path.

    impl="pallas" runs the true paged kernel (block-table lookup in the
    ``index_map``; autotuned page-aligned tiling).  impl="jnp" gathers the
    table view and sweeps one split per page with
    :func:`mla_decode_partials` — the same fixed, geometry-determined
    split structure the rank-sharded ``shard_map`` path partitions across
    devices, so unsharded and sharded streams match bit-for-bit
    (``splits``/``block_k`` are ignored on this path).  impl="ref"
    delegates to the 3-pass oracle over the gathered view.
    """
    if ckv_pages.ndim == 3:
        ckv_pages, krope_pages, ckv_scale, krope_scale = _lift_layer(
            ckv_pages, krope_pages, ckv_scale, krope_scale)
    b, hq, p, e = q.shape
    page_size, rank = ckv_pages.shape[2:]
    rope_dim = krope_pages.shape[-1]
    w = block_table.shape[1]
    if e != rank + rope_dim:
        raise ValueError(f"q last dim {e} != rank {rank} + rope {rope_dim}")
    scale = scale if scale is not None else 1.0 / (e ** 0.5)

    if impl == "auto":
        impl = "pallas" if _on_tpu() else "jnp"

    if impl in ("jnp", "ref"):
        ckv = gather_pages(ckv_pages[layer], block_table)   # [B, W·ps, r]
        kr = gather_pages(krope_pages[layer], block_table)
        if ckv_scale is not None:
            cs = gather_pages(ckv_scale[layer], block_table)    # [B, W·ps]
            ks = gather_pages(krope_scale[layer], block_table)
            ckv = ckv.astype(jnp.float32) * cs.astype(jnp.float32)[..., None]
            kr = kr.astype(jnp.float32) * ks.astype(jnp.float32)[..., None]
        if impl == "ref":
            k = jnp.concatenate([ckv, kr], axis=-1)[:, None]
            v = ckv[:, None]
            return fusemax_decode(
                q, k, v, kv_len, softcap=softcap, scale=scale, impl="ref")
        if p == 1:
            pm, pl_, pnv = mla_decode_partials(
                q, ckv, kr, kv_len, start_page=0, n_splits=w,
                page_size=page_size, scale=scale, softcap=softcap)
            return mla_combine_partials(pm, pl_, pnv, q.dtype)
        pm, pl_, pnv = mla_verify_partials(
            q, ckv, kr, kv_len, start_page=0, n_splits=w,
            page_size=page_size, scale=scale, softcap=softcap)
        return mla_verify_combine(pm, pl_, pnv, q.dtype)

    if impl != "pallas":
        raise ValueError(f"unknown impl: {impl}")

    if splits is None or block_k is None:
        tuned = autotune.mla_paged_decode_params(
            w, page_size, max(hq, 8), rank, rope_dim,
            backend=jax.default_backend(), impl=impl,
            elem_bytes=jnp.dtype(ckv_pages.dtype).itemsize)
        splits = tuned.splits if splits is None else splits
        block_k = tuned.block_k if block_k is None else block_k
    splits = max(1, min(splits, w))
    while w % splits:
        splits -= 1
    block_k = min(block_k, page_size)
    while page_size % block_k:
        block_k -= 1
    block_k = autotune.verify_block_k(
        block_k, p=p, g=max(hq, 8), e=rank + rope_dim, f=rank)
    block_k = _scale_block_k(block_k, page_size, ckv_scale is not None)

    interpret = (not _on_tpu()) if interpret is None else interpret
    out = fusemax_mla_decode_paged_pallas(
        _fold_decode_q(q, b, 1, hq, e), ckv_pages, krope_pages,
        block_table, kv_len, layer,
        scale=scale, softcap=softcap, splits=splits, block_k=block_k,
        exp_impl=exp_impl, interpret=interpret, p=p,
        ckv_scale=ckv_scale, krope_scale=krope_scale,
    )
    return _unfold_decode_out(out, b, 1, hq, rank, p=p)


def fusemax_decode(
    q: jnp.ndarray,         # [B, Hq, 1, E]
    k: jnp.ndarray,         # [B, Hkv, M, E]  (cache, padded to M slots)
    v: jnp.ndarray,         # [B, Hkv, M, F]
    kv_len: jnp.ndarray,    # [B] valid lengths (the query is token kv_len-1)
    *,
    softcap: Optional[float] = None,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    impl: str = "auto",
    splits: Optional[int] = None,
    block_k: Optional[int] = None,
    exp_impl: str = "native",
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Decode against a ragged KV cache (split-K FuseMax).

    P = 1 is the plain decode step.  P > 1 is the speculative *verify*
    dispatch: the P queries are a draft chain occupying logical positions
    ``kv_len - 1 + j`` (``kv_len`` includes query 0) and each attends
    causally to keys ``< kv_len + j``.  ``splits`` / ``block_k`` left as
    ``None`` are resolved by the autotuner per (cache length, backend) —
    the key never sees P, so verify inherits exactly the split geometry
    of single-token decode and per-position outputs are bit-identical.
    """
    b, hq, p, e = q.shape
    _, hkv, m, f = v.shape
    if p != 1 and window is not None:
        raise ValueError(
            "multi-query verify does not support windowed attention "
            "(draft positions would need per-query ring views)")
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / (e ** 0.5)

    if impl == "auto":
        impl = "pallas" if _on_tpu() else "jnp"

    if splits is None or block_k is None:
        tuned = autotune.decode_params(
            m, max(group, 8), e, f, backend=jax.default_backend(), impl=impl)
        splits = tuned.splits if splits is None else splits
        block_k = tuned.block_k if block_k is None else block_k
    splits = max(1, min(splits, m // min(m, block_k)))
    while m % splits:
        splits -= 1

    if impl == "ref":
        if p == 1:
            return _ref.decode_reference(
                q, k, v, kv_len, softcap=softcap, window=window,
                scale=scale)
        # k-step oracle: each chain position is an independent one-token
        # decode at its own effective length
        outs = [_ref.decode_reference(
                    q[:, :, j:j + 1], k, v, kv_len + j,
                    softcap=softcap, window=window, scale=scale)
                for j in range(p)]
        return jnp.concatenate(outs, axis=2)
    if impl == "jnp":
        if p == 1:
            return _decode_splitk_jnp(
                q, k, v, kv_len, scale=scale, softcap=softcap,
                window=window, splits=splits)
        return _verify_splitk_jnp(
            q, k, v, kv_len, scale=scale, softcap=softcap, splits=splits)
    if impl != "pallas":
        raise ValueError(f"unknown impl: {impl}")

    interpret = (not _on_tpu()) if interpret is None else interpret
    block_k = autotune.verify_block_k(
        block_k, p=p, g=max(group, 8), e=e, f=f)
    out = fusemax_decode_pallas(
        _fold_decode_q(q, b, hkv, group, e),
        k.reshape(b * hkv, m, e),
        v.reshape(b * hkv, m, f),
        kv_len,
        scale=scale, softcap=softcap, window=window, hkv=hkv,
        splits=splits, block_k=block_k, exp_impl=exp_impl,
        interpret=interpret, p=p,
    )
    return _unfold_decode_out(out, b, hkv, group, f, p=p)
