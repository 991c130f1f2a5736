"""FuseMax split-K decode kernel ("flash-decoding" over Cascade 5).

Decode offers no query-row parallelism (P = 1 token per sequence), so the
1-pass cascade is instantiated *twice*:

  1. A Pallas kernel sweeps each of S disjoint M-chunks with the usual
     running (RM, RD, RNV) state and emits per-chunk partials — the grid is
     ``(B·Hkv, S, M2)``, S parallel, M2 sequential.
  2. The partials combine with exactly the running-max algebra of Eqs.
     48-52 (it is associative), done in jnp — O(S·G) work.

Ragged KV lengths (each sequence in the batch has its own valid prefix of
the cache) arrive via scalar prefetch (SMEM) and mask the tail chunks.

The *paged* variant (``fusemax_decode_paged_pallas``) reads K/V from a
head-major page pool ``[L, num_pages, Hkv, page_size, E]`` stacked over a
run's layers, through a per-sequence block table instead of a dense
cache: the block table and the layer index ride in as the second and
third scalar-prefetch operands and the K/V ``index_map``s resolve each
tile's (layer, page) from them, so the sweep touches only the pages the
sequence owns, in the stacked pool the layer loop carries (no layer's
pool is ever sliced out).  One grid step covers every kv head of a page
slice.
Split boundaries stay page-aligned (``splits`` divides the table width,
``block_k`` divides ``page_size``) and the partials combine with the same
associative running-max algebra — the cascade is indifferent to where the
keys physically live.

Both kernels are grid-parallel over (batch, kv-head) fibers with no
cross-head communication, which is what lets the serving tier run them
on kv-head *shards* of a device-partitioned page pool (``shard_map`` in
``repro.model.attention``): a shard's ``hkv`` is just a smaller fiber
count, the block table and page ids are global, and per-fiber results
match the full-pool run bit-for-bit.

The MLA variant (``fusemax_mla_decode_paged_pallas``) runs the same sweep
in *latent space*: the page pool stores compressed latents
``ckv [P, ps, r]`` + positional keys ``krope [P, ps, rope_dim]`` (Hkv = 1,
group = every q head), scores are the absorbed form
``q_nopeᵀW_uk·ckv + q_ropeᵀ·krope`` (two dots against the two page
streams, summed), and the accumulator is the latent ``Σ a·ckv`` — no
per-head K/V is ever materialized.  MLA pools shard on the *rank* axis,
which every score contracts over, so the serving tier parallelizes MLA
decode differently: each device sweeps a contiguous 1/tp strip of the
block table's pages (one split per page), all-gathers the page-ordered
(RM, RD, RNV) partial stacks, and runs the identical associative combine
replicated — per-device FLOPs are 1/tp while the combined output stays
bit-identical to the single-device sweep.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.einsum import Cascade, Einsum, T
from repro.kernels.fusemax import LANES, NEG_INF, _exp


def _decode_partials_kernel(
    kv_len_ref,                     # SMEM scalar-prefetch: [B] int32
    q_ref, k_ref, v_ref,
    pm_ref, pl_ref, pnv_ref,        # partial outputs per (bh, s)
    m_scratch, l_scratch, acc_scratch,
    *,
    scale: float,
    softcap: Optional[float],
    window: Optional[int],
    hkv: int,
    block_k: int,
    m2_total: int,
    split_len: int,
    exp_impl: str,
    n_pos: int = 1,
    rows_per_pos: int = 0,
):
    bh = pl.program_id(0)
    s = pl.program_id(1)
    m2 = pl.program_id(2)

    kv_len = kv_len_ref[bh // hkv]           # valid cache prefix for this seq
    q_pos = kv_len - 1                       # the query is the newest token

    @pl.when(m2 == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    k_lo = s * split_len + m2 * block_k
    # verify chains (p > 1): the last draft position sees p-1 extra keys
    run = k_lo < kv_len + (n_pos - 1)
    if window is not None:
        run &= (k_lo + block_k - 1) > q_pos - window

    @pl.when(run)
    def _body():
        g = q_ref.shape[1]
        q_tile = q_ref[0].astype(jnp.float32)            # [G, E]
        k_tile = k_ref[0, 0].astype(jnp.float32)         # [block_k, E]
        v_tile = v_ref[0, 0].astype(jnp.float32)         # [block_k, F]

        sc = jax.lax.dot_general(
            q_tile, k_tile, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                        # [G, block_k]
        if softcap is not None:
            sc = softcap * jnp.tanh(sc / softcap)

        cols = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        kpos = k_lo + cols
        if n_pos == 1:
            ok = kpos < kv_len                           # ragged mask
        else:
            # row r carries draft position r // rows_per_pos, which
            # attends causally to keys < kv_len + position
            rows = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0)
            ok = kpos < kv_len + rows // rows_per_pos
        if window is not None:
            ok &= kpos > q_pos - window
        sc = jnp.where(ok, sc, NEG_INF)

        m_prev = m_scratch[:, :1]
        lm = jnp.max(sc, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, lm)
        p = _exp(sc - m_new, exp_impl)
        sld = jnp.sum(p, axis=1, keepdims=True)
        prm = _exp(m_prev - m_new, exp_impl)
        l_scratch[...] = jnp.broadcast_to(
            l_scratch[:, :1] * prm + sld, l_scratch.shape)
        acc_scratch[...] = acc_scratch[...] * prm + jax.lax.dot_general(
            p, v_tile, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scratch[...] = jnp.broadcast_to(m_new, m_scratch.shape)

    @pl.when(m2 == m2_total - 1)
    def _finish():
        pm_ref[0, 0] = m_scratch[...].astype(pm_ref.dtype)
        pl_ref[0, 0] = l_scratch[...].astype(pl_ref.dtype)
        pnv_ref[0, 0] = acc_scratch[...].astype(pnv_ref.dtype)


def fusemax_decode_pallas(
    q: jnp.ndarray,        # [BHkv, G, E]  (G = q heads per kv head, padded ≥8)
    k: jnp.ndarray,        # [BHkv, Mp, E]
    v: jnp.ndarray,        # [BHkv, Mp, F]
    kv_len: jnp.ndarray,   # [B] int32 valid lengths
    *,
    scale: float,
    softcap: Optional[float] = None,
    window: Optional[int] = None,
    hkv: int,
    splits: int = 8,
    block_k: int = 256,
    exp_impl: str = "native",
    interpret: bool = False,
    p: int = 1,
) -> jnp.ndarray:
    """Split-K FuseMax decode. Returns [BHkv, G, F] (q.dtype).

    With ``p > 1`` the G axis is a folded verify chain (p positions ×
    G/p heads, see ``ops._fold_decode_q``): row r is draft position
    r // (G/p), which attends to keys < kv_len + position."""
    bh, g, e = q.shape
    _, mp, f = v.shape
    if g % p:
        raise ValueError(f"folded q rows {g} not divisible by p={p}")
    if window is not None and p != 1:
        raise ValueError("multi-query verify does not support windows")
    if mp % splits:
        raise ValueError(f"M={mp} not divisible by splits={splits}")
    split_len = mp // splits
    block_k = min(block_k, split_len)
    if split_len % block_k:
        raise ValueError(f"split_len={split_len} % block_k={block_k}")
    m2 = split_len // block_k
    grid = (bh, splits, m2)

    kernel = functools.partial(
        _decode_partials_kernel,
        scale=scale,
        softcap=softcap,
        window=window,
        hkv=hkv,
        block_k=block_k,
        m2_total=m2,
        split_len=split_len,
        exp_impl=exp_impl,
        n_pos=p,
        rows_per_pos=g // p,
    )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, g, e), lambda b, s, m2, *_: (b, 0, 0)),
            pl.BlockSpec((1, 1, block_k, e),
                         lambda b, s, m2, *_: (b, s, m2, 0)),
            pl.BlockSpec((1, 1, block_k, f),
                         lambda b, s, m2, *_: (b, s, m2, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, LANES), lambda b, s, m2, *_: (b, s, 0, 0)),
            pl.BlockSpec((1, 1, g, LANES), lambda b, s, m2, *_: (b, s, 0, 0)),
            pl.BlockSpec((1, 1, g, f), lambda b, s, m2, *_: (b, s, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, LANES), jnp.float32),
            pltpu.VMEM((g, LANES), jnp.float32),
            pltpu.VMEM((g, f), jnp.float32),
        ],
    )

    k4 = k.reshape(bh, splits, split_len, e)
    v4 = v.reshape(bh, splits, split_len, f)
    pm, pl_, pnv = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bh, splits, g, LANES), jnp.float32),
            jax.ShapeDtypeStruct((bh, splits, g, LANES), jnp.float32),
            jax.ShapeDtypeStruct((bh, splits, g, f), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(kv_len.astype(jnp.int32), q, k4, v4)

    return _combine_partials(pm, pl_, pnv, q.dtype)


def _combine_partials(pm, pl_, pnv, dtype):
    """Combine split-K partials (associative running-max algebra,
    Eqs. 48-52) — shared by the dense and paged kernels."""
    pm = pm[..., 0]                          # [BHkv, S, G]
    pl_ = pl_[..., 0]
    gm = jnp.max(pm, axis=1, keepdims=True)
    cf = jnp.exp(pm - gm)                    # per-split correction factor
    rd = jnp.sum(pl_ * cf, axis=1)           # [BHkv, G]
    rnv = jnp.sum(pnv * cf[..., None], axis=1)
    rd = jnp.where(rd == 0.0, 1.0, rd)
    return (rnv / rd[..., None]).astype(dtype)


def _scale_operand(scale: jnp.ndarray) -> jnp.ndarray:
    """fp16 scale pools enter the kernels bit-cast to int16: Mosaic cannot
    load fp16 vectors on TPU, and a same-width bitcast costs no copy."""
    if scale.dtype == jnp.float16:
        return jax.lax.bitcast_convert_type(scale, jnp.int16)
    return scale


def _layer_operand(layer) -> jnp.ndarray:
    """The layer index of a stacked pool as the [1] int32 scalar-prefetch
    operand the paged kernels' ``index_map``s read."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


def _scale_f32(tile: jnp.ndarray) -> jnp.ndarray:
    """A scale tile as fp32, decoding the fp16 bits of an int16 tile (see
    :func:`_scale_operand`).  Stored scales are finite, so the decode
    covers normal and subnormal fp16 values only."""
    if tile.dtype != jnp.int16:
        return tile.astype(jnp.float32)
    bits = tile.astype(jnp.int32) & 0xFFFF
    sign = bits >> 15
    expo = (bits >> 10) & 0x1F
    mant = bits & 0x3FF
    normal = jax.lax.bitcast_convert_type(
        (sign << 31) | ((expo + 112) << 23) | (mant << 13), jnp.float32)
    sub = mant.astype(jnp.float32) * (2.0 ** -24)
    sub = jnp.where(sign == 1, -sub, sub)
    return jnp.where(expo == 0, sub, normal)


def _paged_decode_partials_kernel(
    kv_len_ref,                     # SMEM scalar-prefetch: [B] int32
    bt_ref,                         # SMEM scalar-prefetch: [B, W] int32
    layer_ref,                      # SMEM scalar-prefetch: [1] int32
    q_ref, k_ref, v_ref,
    *refs,                          # [ks_ref, vs_ref,] outputs, scratch
    scale: float,
    softcap: Optional[float],
    block_k: int,
    m2_total: int,
    split_len: int,
    exp_impl: str,
    n_pos: int = 1,
    rows_per_pos: int = 0,
    quantized: bool = False,
):
    """Same running-state sweep as :func:`_decode_partials_kernel`, for
    every kv head of one sequence at once: the K/V tiles
    ``[Hkv, block_k, E]`` were block-selected through the block table (see
    the ``index_map``s in :func:`fusemax_decode_paged_pallas`), the scores
    and the accumulator are head-batched matmuls, and the kernel body only
    needs the *logical* token index for ragged masking.

    With ``quantized=True`` two scale tiles ``[Hkv, block_k]`` (one scalar
    per (kv head, token)) ride along.  A per-token K scale multiplies that
    token's score column and a per-token V scale its probability column,
    so both apply along the lane axis and the GEMMs run on the raw codes
    (cast to fp32) — the same arithmetic as dequantizing the tiles first."""
    if quantized:
        (ks_ref, vs_ref, pm_ref, pl_ref, pnv_ref,
         m_scratch, l_scratch, acc_scratch) = refs
    else:
        ks_ref = vs_ref = None
        (pm_ref, pl_ref, pnv_ref,
         m_scratch, l_scratch, acc_scratch) = refs
    b = pl.program_id(0)
    s = pl.program_id(1)
    m2 = pl.program_id(2)

    kv_len = kv_len_ref[b]                   # valid logical prefix

    @pl.when(m2 == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    k_lo = s * split_len + m2 * block_k      # logical token index
    run = k_lo < kv_len + (n_pos - 1)            # chain tail sees p-1 extra keys

    @pl.when(run)
    def _body():
        q_tile = q_ref[...].astype(jnp.float32)          # [Hkv, G, E]
        k_tile = k_ref[0].astype(jnp.float32)            # [Hkv, block_k, E]
        v_tile = v_ref[0].astype(jnp.float32)            # [Hkv, block_k, F]

        sc = jax.lax.dot_general(
            q_tile, k_tile, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                                # [Hkv, G, block_k]
        if ks_ref is not None:
            sc = sc * _scale_f32(ks_ref[0])[:, None, :]
        sc = sc * scale
        if softcap is not None:
            sc = softcap * jnp.tanh(sc / softcap)

        cols = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 2)
        if n_pos == 1:
            ok = (k_lo + cols) < kv_len                  # ragged mask
        else:
            # causal intra-draft mask: folded row r is draft position
            # r // rows_per_pos and sees keys < kv_len + position
            rows = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
            ok = (k_lo + cols) < kv_len + rows // rows_per_pos
        sc = jnp.where(ok, sc, NEG_INF)

        m_prev = m_scratch[:, :, :1]
        lm = jnp.max(sc, axis=2, keepdims=True)
        m_new = jnp.maximum(m_prev, lm)
        p = _exp(sc - m_new, exp_impl)
        sld = jnp.sum(p, axis=2, keepdims=True)
        prm = _exp(m_prev - m_new, exp_impl)
        l_scratch[...] = jnp.broadcast_to(
            l_scratch[:, :, :1] * prm + sld, l_scratch.shape)
        if vs_ref is not None:
            p = p * _scale_f32(vs_ref[0])[:, None, :]
        acc_scratch[...] = acc_scratch[...] * prm + jax.lax.dot_general(
            p, v_tile, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        m_scratch[...] = jnp.broadcast_to(m_new, m_scratch.shape)

    @pl.when(m2 == m2_total - 1)
    def _finish():
        pm_ref[:, 0] = m_scratch[...].astype(pm_ref.dtype)
        pl_ref[:, 0] = l_scratch[...].astype(pl_ref.dtype)
        pnv_ref[:, 0] = acc_scratch[...].astype(pnv_ref.dtype)


def fusemax_decode_paged_pallas(
    q: jnp.ndarray,            # [BHkv, G, E]  (G padded ≥ 8)
    k_pages: jnp.ndarray,      # [L, P, Hkv, page_size, E]
    v_pages: jnp.ndarray,      # [L, P, Hkv, page_size, F]
    block_table: jnp.ndarray,  # [B, W] int32 page ids
    kv_len: jnp.ndarray,       # [B] int32 valid logical lengths
    layer,                     # int32 scalar: the layer of L to read
    *,
    scale: float,
    softcap: Optional[float] = None,
    hkv: int,
    splits: int = 1,
    block_k: int = 128,
    exp_impl: str = "native",
    interpret: bool = False,
    p: int = 1,
    k_scale: Optional[jnp.ndarray] = None,   # [L, P, Hkv, page_size]
    v_scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Paged split-K FuseMax decode. Returns [BHkv, G, F] (q.dtype).
    With ``p > 1`` the G axis folds a verify chain (see the dense kernel).

    The pools are head-major and stacked over layers
    (``[L, P, Hkv, page_size, E]``), and one grid step loads a
    ``[Hkv, block_k, E]`` tile — every kv head of one page slice of layer
    ``layer`` — so each block's last two dims are (``block_k``, full
    ``E``), which the TPU tiling accepts.  The grid sweeps logical token
    chunks per sequence; each tile's physical page is looked up in the
    block table inside the ``index_map`` (standard paged attention: the
    gather happens in the pipeline's block fetch, never as a materialized
    [B, T, E] copy), and ``layer`` (a scalar-prefetch operand, traced
    inside a layer loop) picks the layer, so the DMA reads the stacked pool
    in place.

    ``k_scale``/``v_scale`` (quantized pools) stream per-token scale
    tiles ``[Hkv, block_k]`` through the same block-table ``index_map``.
    A scale tile's last dim must be the whole page or a multiple of 128
    lanes, so quantized pools need ``block_k == page_size`` or
    ``block_k % 128 == 0``.
    """
    bh, g, e = q.shape
    _, n_pages, hkv_p, page_size, f = v_pages.shape
    b, w = block_table.shape
    if g % p:
        raise ValueError(f"folded q rows {g} not divisible by p={p}")
    if hkv_p != hkv:
        raise ValueError(f"pages carry Hkv={hkv_p}, caller says {hkv}")
    if bh != b * hkv:
        raise ValueError(f"q batch {bh} != B·Hkv = {b}·{hkv}")
    if w % splits:
        raise ValueError(f"table width {w} not divisible by splits={splits}")
    block_k = min(block_k, page_size)
    if page_size % block_k:
        raise ValueError(f"page_size={page_size} % block_k={block_k}")
    quantized = k_scale is not None
    if quantized and block_k != page_size and block_k % 128:
        raise ValueError(
            f"quantized pools tile scales [Hkv, block_k]: block_k={block_k} "
            f"must equal page_size={page_size} or be a multiple of 128")
    split_pages = w // splits
    split_len = split_pages * page_size
    blocks_per_page = page_size // block_k
    m2 = split_pages * blocks_per_page
    grid = (b, splits, m2)

    kernel = functools.partial(
        _paged_decode_partials_kernel,
        scale=scale,
        softcap=softcap,
        block_k=block_k,
        m2_total=m2,
        split_len=split_len,
        exp_impl=exp_impl,
        n_pos=p,
        rows_per_pos=g // p,
        quantized=quantized,
    )

    def _page(b_i, s, m2_i, bt_ref):
        page_slot = s * split_pages + m2_i // blocks_per_page
        # unbacked table rows hold the out-of-range sentinel id (P):
        # clamp the DMA to the last page — those tiles are masked by
        # kv_len in the kernel body, so the content never contributes
        return jnp.minimum(bt_ref[b_i, page_slot], n_pages - 1)

    def _kv_index(b_i, s, m2_i, kv_len_ref, bt_ref, layer_ref):
        return (layer_ref[0], _page(b_i, s, m2_i, bt_ref), 0,
                m2_i % blocks_per_page, 0)

    def _scale_index(b_i, s, m2_i, kv_len_ref, bt_ref, layer_ref):
        return (layer_ref[0], _page(b_i, s, m2_i, bt_ref), 0,
                m2_i % blocks_per_page)

    in_specs = [
        pl.BlockSpec((hkv, g, e), lambda b_i, s, m2_i, *_: (b_i, 0, 0)),
        pl.BlockSpec((None, 1, hkv, block_k, e), _kv_index),
        pl.BlockSpec((None, 1, hkv, block_k, f), _kv_index),
    ]
    operands = [q, k_pages, v_pages]
    if quantized:
        in_specs += [
            pl.BlockSpec((None, 1, hkv, block_k), _scale_index),
            pl.BlockSpec((None, 1, hkv, block_k), _scale_index),
        ]
        operands += [_scale_operand(k_scale), _scale_operand(v_scale)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((hkv, 1, g, LANES),
                         lambda b_i, s, m2_i, *_: (b_i, s, 0, 0)),
            pl.BlockSpec((hkv, 1, g, LANES),
                         lambda b_i, s, m2_i, *_: (b_i, s, 0, 0)),
            pl.BlockSpec((hkv, 1, g, f),
                         lambda b_i, s, m2_i, *_: (b_i, s, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((hkv, g, LANES), jnp.float32),
            pltpu.VMEM((hkv, g, LANES), jnp.float32),
            pltpu.VMEM((hkv, g, f), jnp.float32),
        ],
    )

    pm, pl_, pnv = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bh, splits, g, LANES), jnp.float32),
            jax.ShapeDtypeStruct((bh, splits, g, LANES), jnp.float32),
            jax.ShapeDtypeStruct((bh, splits, g, f), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name="fusemax_decode_paged",
        interpret=interpret,
    )(kv_len.astype(jnp.int32), block_table.astype(jnp.int32),
      _layer_operand(layer), *operands)

    return _combine_partials(pm, pl_, pnv, q.dtype)


# ---------------------------------------------------------------------------
# Declared cascades (checked against the kernels by repro.analysis)
# ---------------------------------------------------------------------------

def _splitk_cascade(
    name: str,
    *,
    query_ranks: tuple[str, ...] = ("G",),
    mla: bool = False,
    causal_chain: bool = False,
) -> Cascade:
    """The split-K instantiation of Cascade 5 as a symbolic cascade.

    M is partitioned into (S, M2, M0): S independent splits (grid-parallel),
    M2 the per-split *iterative* rank (the sequential grid dimension
    carrying the RM/RD/RNV running state), M0 the VMEM tile.  Per-split
    partials (PM, PD, PNV) are single final reads of the running state;
    the combine stage is the associative running-max algebra of Eqs. 48-52
    over the S axis — partial-M bookkeeping (O(S·G) work), not a pass.

    ``mla`` switches to the absorbed-score MLA form: the latent page
    stream BC plays both K (scores contract the latent rank R against the
    W_uk-absorbed queries, plus a rope dot) and V (the accumulator lives
    in latent space) — BC is read twice, but both reads sit in the same
    pass generation, so the cascade stays 1-pass with O(1) live state.

    ``causal_chain`` adds the k+1-token verify chain: the extra free query
    rank C rides every query-side tensor and the intra-draft causal mask
    is a *filtered* consumption of M (``m < kv_len + c``) — filtering
    touches a subset of each fiber and never acts as a pass barrier.
    """
    qr = query_ranks
    c = Cascade(name)
    c.partition("M", ("S", "M2", "M0"))
    blk = ("S", "M2", "M0")
    it = ("S", "M2*")       # running state: per-split, iterative over M2
    if mla:
        # latent pages [R, M] double as K and V; rope pages [O, M] are
        # score-only.  Queries arrive absorbed: QN[R, ...] ⊕ QR[O, ...].
        c.add(Einsum(T("BC", "R", *blk), (T("CKV", "R", "M"),), init=True))
        c.add(Einsum(T("BR", "O", *blk), (T("KR", "O", "M"),), init=True))
        v_rank = "R"
    else:
        c.add(Einsum(T("BK", "E", *blk), (T("K", "E", "M"),), init=True))
        c.add(Einsum(T("BV", "F", *blk), (T("V", "F", "M"),), init=True))
        v_rank = "F"
    c.add(Einsum(T("RM", *it, *qr), (), init=True))
    c.add(Einsum(T("RD", *it, *qr), (), init=True))
    c.add(Einsum(T("RNV", v_rank, *it, *qr), (), init=True))

    if mla:
        score_in = (T("QN", "R", *qr), T("BC", "R", *blk),
                    T("QR", "O", *qr), T("BR", "O", *blk))
    else:
        score_in = (T("Q", "E", *qr), T("BK", "E", *blk))
    if causal_chain:
        # intra-draft causal mask: position c sees keys m < kv_len + c
        score_in = (*score_in, T("CM", "M<=C", "C"))
    c.add(Einsum(T("BQK", *blk, *qr), score_in))                   # Eq. 42
    c.add(Einsum(T("LM", "S", "M2", *qr),
                 (T("BQK", *blk, *qr),), reduce_op="max"))         # Eq. 43
    c.add(Einsum(T("RM", *it, *qr),
                 (T("RM", *it, *qr), T("LM", *it, *qr)),
                 compute="max"))                                   # Eq. 44
    c.add(Einsum(T("SLN", *blk, *qr),
                 (T("BQK", *blk, *qr), T("RM", *it, *qr)),
                 compute="exp-sub"))                               # Eq. 45
    c.add(Einsum(T("SLD", "S", "M2", *qr), (T("SLN", *blk, *qr),)))  # Eq. 46
    c.add(Einsum(T("SLNV", v_rank, "S", "M2", *qr),
                 (T("SLN", *blk, *qr),
                  T("BC" if mla else "BV", v_rank, *blk))))        # Eq. 47
    c.add(Einsum(T("PRM", *it, *qr),
                 (T("RM", *it, *qr),), compute="exp-sub"))         # Eq. 48
    c.add(Einsum(T("SPD", "S", "M2", *qr),
                 (T("RD", *it, *qr), T("PRM", *it, *qr))))         # Eq. 49
    c.add(Einsum(T("RD", *it, *qr),
                 (T("SLD", *it, *qr), T("SPD", *it, *qr))))        # Eq. 50
    c.add(Einsum(T("SPNV", v_rank, "S", "M2", *qr),
                 (T("RNV", v_rank, *it, *qr), T("PRM", *it, *qr))))  # Eq. 51
    c.add(Einsum(T("RNV", v_rank, *it, *qr),
                 (T("SLNV", v_rank, *it, *qr),
                  T("SPNV", v_rank, *it, *qr))))                   # Eq. 52
    # per-split partials: the emitted (PM, PD, PNV) stacks — single final
    # reads of each split's running state (not passes over M)
    c.add(Einsum(T("PM", "S", *qr), (T("RM", "S", "M2$", *qr),)))
    c.add(Einsum(T("PD", "S", *qr), (T("RD", "S", "M2$", *qr),)))
    c.add(Einsum(T("PNV", v_rank, "S", *qr),
                 (T("RNV", v_rank, "S", "M2$", *qr),)))
    # combine: associative running-max algebra over S (_combine_partials)
    c.add(Einsum(T("GM", *qr), (T("PM", "S", *qr),), reduce_op="max"))
    c.add(Einsum(T("CF", "S", *qr),
                 (T("PM", "S", *qr), T("GM", *qr)), compute="exp-sub"))
    c.add(Einsum(T("SD", *qr), (T("PD", "S", *qr), T("CF", "S", *qr))))
    c.add(Einsum(T("SNV", v_rank, *qr),
                 (T("PNV", v_rank, "S", *qr), T("CF", "S", *qr))))
    c.add(Einsum(T("AV", v_rank, *qr),
                 (T("SNV", v_rank, *qr), T("SD", *qr)),
                 compute="÷"))                                     # Eq. 53
    return c


def decode_splitk_cascade() -> Cascade:
    """Dense split-K decode (:func:`fusemax_decode_pallas` and the jnp
    ``_decode_splitk_jnp`` mirror): 1 pass over M, O(1) live state."""
    return _splitk_cascade("decode-splitk-1pass")


def decode_paged_cascade() -> Cascade:
    """Paged split-K decode (:func:`fusemax_decode_paged_pallas`): same
    cascade as the dense kernel — the block-table ``index_map`` changes
    where tiles physically live, never how often they are read."""
    return _splitk_cascade("decode-paged-splitk-1pass")


def mla_decode_paged_cascade() -> Cascade:
    """Paged MLA absorbed-score decode
    (:func:`fusemax_mla_decode_paged_pallas`): the latent stream BC feeds
    both the score dot and the rank-space accumulator — two same-pass
    reads, still 1-pass with an O(G·R) accumulator."""
    return _splitk_cascade("mla-decode-paged-1pass", mla=True)


def verify_chain_cascade() -> Cascade:
    """k+1-token draft-chain verify (GQA kernels with ``p > 1``): the
    chain rank C is a free query rank; the intra-draft causal mask is a
    filtered consumption of M.  Accumulators are O((k+1)·G) — independent
    of the cache length."""
    return _splitk_cascade("verify-chain-1pass",
                           query_ranks=("C", "G"), causal_chain=True)


def mla_verify_chain_cascade() -> Cascade:
    """MLA variant of the verify chain (absorbed scores, latent
    accumulator, free chain rank C)."""
    return _splitk_cascade("mla-verify-chain-1pass", mla=True,
                           query_ranks=("C", "G"), causal_chain=True)


def _mla_paged_decode_partials_kernel(
    kv_len_ref,                     # SMEM scalar-prefetch: [B] int32
    bt_ref,                         # SMEM scalar-prefetch: [B, W] int32
    layer_ref,                      # SMEM scalar-prefetch: [1] int32
    q_ref, ckv_ref, krope_ref,
    *refs,                          # [cs_ref, krs_ref,] outputs, scratch
    scale: float,
    softcap: Optional[float],
    rank: int,
    block_k: int,
    m2_total: int,
    split_len: int,
    exp_impl: str,
    n_pos: int = 1,
    rows_per_pos: int = 0,
    quantized: bool = False,
):
    """Latent-space (MLA absorbed-form) variant of
    :func:`_paged_decode_partials_kernel`.  The query tile carries the
    W_uk-absorbed queries concatenated with the rope queries
    ``[G, rank + rope_dim]``; the score against a latent page tile is the
    sum of two dots (latent and rope halves) and the value stream IS the
    latent tile — the accumulator lives in rank-space.

    With ``quantized=True`` two per-token scale tiles ``[1, block_k]``
    (one scalar per latent vector / rope vector) ride along.  They scale
    the score columns of their dot and, for the latent scale, the
    probability columns of the rank-space accumulation — the arithmetic
    of dequantizing the tiles first, applied along the lane axis."""
    if quantized:
        (cs_ref, krs_ref, pm_ref, pl_ref, pnv_ref,
         m_scratch, l_scratch, acc_scratch) = refs
    else:
        cs_ref = krs_ref = None
        (pm_ref, pl_ref, pnv_ref,
         m_scratch, l_scratch, acc_scratch) = refs
    b = pl.program_id(0)
    s = pl.program_id(1)
    m2 = pl.program_id(2)

    kv_len = kv_len_ref[b]                   # valid logical prefix

    @pl.when(m2 == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    k_lo = s * split_len + m2 * block_k      # logical token index

    @pl.when(k_lo < kv_len + (n_pos - 1))
    def _body():
        q_tile = q_ref[0].astype(jnp.float32)            # [G, r + rope]
        ckv_tile = ckv_ref[0].astype(jnp.float32)        # [block_k, r]
        kr_tile = krope_ref[0].astype(jnp.float32)       # [block_k, rope]

        sc_lat = jax.lax.dot_general(
            q_tile[:, :rank], ckv_tile, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        sc_rope = jax.lax.dot_general(
            q_tile[:, rank:], kr_tile, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if cs_ref is not None:
            cs = _scale_f32(cs_ref[0])                   # [1, block_k]
            sc_lat = sc_lat * cs
            sc_rope = sc_rope * _scale_f32(krs_ref[0])
        sc = (sc_lat + sc_rope) * scale                  # [G, block_k]
        if softcap is not None:
            sc = softcap * jnp.tanh(sc / softcap)

        cols = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        if n_pos == 1:
            ok = (k_lo + cols) < kv_len                  # ragged mask
        else:
            rows = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0)
            ok = (k_lo + cols) < kv_len + rows // rows_per_pos
        sc = jnp.where(ok, sc, NEG_INF)

        m_prev = m_scratch[:, :1]
        lm = jnp.max(sc, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, lm)
        p = _exp(sc - m_new, exp_impl)
        sld = jnp.sum(p, axis=1, keepdims=True)
        prm = _exp(m_prev - m_new, exp_impl)
        l_scratch[...] = jnp.broadcast_to(
            l_scratch[:, :1] * prm + sld, l_scratch.shape)
        if cs_ref is not None:
            p = p * cs
        acc_scratch[...] = acc_scratch[...] * prm + jax.lax.dot_general(
            p, ckv_tile, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scratch[...] = jnp.broadcast_to(m_new, m_scratch.shape)

    @pl.when(m2 == m2_total - 1)
    def _finish():
        pm_ref[0, 0] = m_scratch[...].astype(pm_ref.dtype)
        pl_ref[0, 0] = l_scratch[...].astype(pl_ref.dtype)
        pnv_ref[0, 0] = acc_scratch[...].astype(pnv_ref.dtype)


def fusemax_mla_decode_paged_pallas(
    q: jnp.ndarray,             # [B, G, rank + rope_dim]  (G padded ≥ 8)
    ckv_pages: jnp.ndarray,     # [L, P, page_size, rank]
    krope_pages: jnp.ndarray,   # [L, P, page_size, rope_dim]
    block_table: jnp.ndarray,   # [B, W] int32 page ids
    kv_len: jnp.ndarray,        # [B] int32 valid logical lengths
    layer,                      # int32 scalar: the layer of L to read
    *,
    scale: float,
    softcap: Optional[float] = None,
    splits: int = 1,
    block_k: int = 128,
    exp_impl: str = "native",
    interpret: bool = False,
    p: int = 1,
    ckv_scale: Optional[jnp.ndarray] = None,   # [L, P, page_size]
    krope_scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Paged split-K MLA decode in latent space. Returns [B, G, rank]
    (q.dtype) — the latent output, before the W_uv up-projection.

    Same structure as :func:`fusemax_decode_paged_pallas` with Hkv = 1 and
    two page streams whose ``index_map``s resolve the same block-table
    slot of layer ``layer`` of the stacked pools: the latent pages double
    as the V stream (F = rank), so per-step decode DMAs exactly the slot's
    pages and nothing else.

    Quantized scale pools enter the kernel as ``[L, P, 1, page_size]``
    views tiled ``(1, 1, block_k)``, so ``block_k`` must be the whole page
    or a multiple of 128 lanes.
    """
    b, g, e = q.shape
    n_layers, n_pages, page_size, rank = ckv_pages.shape
    rope_dim = krope_pages.shape[-1]
    bt_b, w = block_table.shape
    if g % p:
        raise ValueError(f"folded q rows {g} not divisible by p={p}")
    if e != rank + rope_dim:
        raise ValueError(f"q last dim {e} != rank {rank} + rope {rope_dim}")
    if bt_b != b:
        raise ValueError(f"q batch {b} != block table rows {bt_b}")
    if w % splits:
        raise ValueError(f"table width {w} not divisible by splits={splits}")
    block_k = min(block_k, page_size)
    if page_size % block_k:
        raise ValueError(f"page_size={page_size} % block_k={block_k}")
    quantized = ckv_scale is not None
    if quantized and block_k != page_size and block_k % 128:
        raise ValueError(
            f"quantized latent pools tile scales [1, block_k]: "
            f"block_k={block_k} must equal page_size={page_size} or be a "
            f"multiple of 128")
    split_pages = w // splits
    split_len = split_pages * page_size
    blocks_per_page = page_size // block_k
    m2 = split_pages * blocks_per_page
    grid = (b, splits, m2)

    kernel = functools.partial(
        _mla_paged_decode_partials_kernel,
        scale=scale,
        softcap=softcap,
        rank=rank,
        block_k=block_k,
        m2_total=m2,
        split_len=split_len,
        exp_impl=exp_impl,
        n_pos=p,
        rows_per_pos=g // p,
        quantized=quantized,
    )

    def _page(b_i, s, m2_i, bt_ref):
        page_slot = s * split_pages + m2_i // blocks_per_page
        # sentinel ids (P) on unbacked slots clamp to the last page; the
        # kv_len mask in the body keeps their content out of the cascade
        return jnp.minimum(bt_ref[b_i, page_slot], n_pages - 1)

    def _page_index(b_i, s, m2_i, kv_len_ref, bt_ref, layer_ref):
        return (layer_ref[0], _page(b_i, s, m2_i, bt_ref),
                m2_i % blocks_per_page, 0)

    def _scale_index(b_i, s, m2_i, kv_len_ref, bt_ref, layer_ref):
        return (layer_ref[0], _page(b_i, s, m2_i, bt_ref), 0,
                m2_i % blocks_per_page)

    in_specs = [
        pl.BlockSpec((1, g, e), lambda b_i, s, m2_i, *_: (b_i, 0, 0)),
        pl.BlockSpec((None, 1, block_k, rank), _page_index),
        pl.BlockSpec((None, 1, block_k, rope_dim), _page_index),
    ]
    operands = [q, ckv_pages, krope_pages]
    if quantized:
        in_specs += [
            pl.BlockSpec((None, 1, 1, block_k), _scale_index),
            pl.BlockSpec((None, 1, 1, block_k), _scale_index),
        ]
        view = (n_layers, n_pages, 1, page_size)
        operands += [_scale_operand(ckv_scale).reshape(view),
                     _scale_operand(krope_scale).reshape(view)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, g, LANES),
                         lambda b_i, s, m2_i, *_: (b_i, s, 0, 0)),
            pl.BlockSpec((1, 1, g, LANES),
                         lambda b_i, s, m2_i, *_: (b_i, s, 0, 0)),
            pl.BlockSpec((1, 1, g, rank),
                         lambda b_i, s, m2_i, *_: (b_i, s, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, LANES), jnp.float32),
            pltpu.VMEM((g, LANES), jnp.float32),
            pltpu.VMEM((g, rank), jnp.float32),
        ],
    )

    pm, pl_, pnv = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, splits, g, LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, splits, g, LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, splits, g, rank), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name="fusemax_mla_decode_paged",
        interpret=interpret,
    )(kv_len.astype(jnp.int32), block_table.astype(jnp.int32),
      _layer_operand(layer), *operands)

    return _combine_partials(pm, pl_, pnv, q.dtype)
