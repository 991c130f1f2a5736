"""Block-size autotuner for the FuseMax kernels.

Picks ``block_q`` / ``block_k`` (prefill attention) and ``splits`` /
``block_k`` (split-K decode) per (shape, backend) so callers — the model
layers, the serving engine, the benchmarks — never hardcode tile sizes.

Two sources feed the table, in priority order:

  1. **Measured** entries: ``measure_best`` times real candidate calls
     (median of N after warmup) and caches the winner in-process; set
     ``REPRO_AUTOTUNE_CACHE=/path.json`` to persist/reload across runs.
  2. **Modeled** entries: a cost model seeded by the paper's spatial-array
     analysis (:mod:`repro.analysis.accel_model`) — the 128×128 MACC array
     prior sets the base tile (``block = 128``), then the model trades
     padding waste, per-tile dispatch overhead, and the VMEM working-set
     bound O(block_q·E + block_k·(E+F)) (the paper's M-independent
     buffering) to score each candidate.

All lookups go through :func:`attention_params` / :func:`decode_params`;
``fusemax_attention`` / ``fusemax_decode`` call these whenever the caller
leaves ``block_q`` / ``block_k`` / ``splits`` unset.
"""
from __future__ import annotations

import dataclasses
import json
import logging
import os
import statistics
import time
from typing import Callable, Optional, Sequence

from repro.analysis.accel_model import SpatialArch

_ARCH = SpatialArch()
_log = logging.getLogger(__name__)

#: VMEM budget for one kernel instance (bytes).  Half of a 16 MiB TPU VMEM
#: — the other half is Pallas' automatic double-buffering of the K/V
#: streams (fusemax.py docstring; paper Fig. 4 epoch-pipelined fills).
VMEM_BUDGET = 8 * 2**20

#: per-grid-step fixed overhead in "MACC-equivalents" — charges small
#: tiles for their loop/dispatch cost (calibrated vs the 128-lane prior:
#: a 128×128 tile does 128·128·E ≫ overhead, a 8×128 tile does not).
TILE_OVERHEAD = 4096


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def next_pow2(n: int) -> int:
    """Smallest power of two ≥ n (1 for n ≤ 1).  Shared by the shape
    buckets here and the serving engine's admission-width padding."""
    return 1 << max(0, n - 1).bit_length()


@dataclasses.dataclass(frozen=True)
class AttentionParams:
    block_q: int
    block_k: int


@dataclasses.dataclass(frozen=True)
class DecodeParams:
    splits: int
    block_k: int


# ---------------------------------------------------------------------------
# Modeled costs (prior: accel_model's 128×128 2D array)
# ---------------------------------------------------------------------------

def _attention_candidates(p: int, m: int) -> list[AttentionParams]:
    base = _ARCH.pe2d_rows                       # 128 — the paper's array
    bqs = sorted({min(_round_up(p, 8), b) for b in (32, 64, base, 2 * base)})
    bks = sorted({min(_round_up(m, base), b)
                  for b in (base, 2 * base, 4 * base)})
    return [AttentionParams(bq, bk) for bq in bqs for bk in bks]


def _attention_cost(c: AttentionParams, p: int, m: int, e: int, f: int,
                    elem_bytes: int = 4) -> float:
    """Score = padded MACC work + per-tile overhead; ∞ if VMEM-infeasible."""
    vmem = (c.block_q * e + c.block_k * (e + f) + c.block_q * f
            + 2 * c.block_q * 128) * elem_bytes
    if vmem > VMEM_BUDGET:
        return float("inf")
    p_pad = _round_up(p, c.block_q)
    m_pad = _round_up(m, c.block_k)
    n_tiles = (p_pad // c.block_q) * (m_pad // c.block_k)
    work = p_pad * m_pad * (e + f)               # BQK + SLNV MACCs
    return work + n_tiles * TILE_OVERHEAD


def _decode_candidates(m: int) -> list[DecodeParams]:
    base = _ARCH.pe2d_cols                       # 128 — TPU lane width
    out = []
    for splits in (1, 2, 4, 8, 16):
        if splits > m:
            continue
        s = splits
        while m % s:                             # ragged M: shrink to a divisor
            s -= 1
        split_len = m // s
        if split_len < base and s > 1:
            continue                             # sub-lane tiles waste the VPU
        for bk in (base, 2 * base, 4 * base):
            out.append(DecodeParams(s, min(bk, split_len)))
    return list(dict.fromkeys(out))


def _decode_cost(c: DecodeParams, m: int, g: int, e: int, f: int,
                 elem_bytes: int = 4) -> float:
    """Split-K decode: parallel sweep time + O(splits) combine cost."""
    vmem = (g * e + c.block_k * (e + f) + g * f + 2 * g * 128) * elem_bytes
    if vmem > VMEM_BUDGET:
        return float("inf")
    split_len = m // c.splits
    split_len = _round_up(split_len, min(c.block_k, split_len))
    # the S splits run in parallel across cores (grid dim "parallel");
    # critical path is one split's sweep + the combine reduction
    sweep = split_len * g * (e + f)
    n_tiles = max(1, split_len // c.block_k)
    combine = c.splits * g * (f + 2)             # Eqs. 48-52 partial merge
    return sweep + n_tiles * TILE_OVERHEAD + combine


# ---------------------------------------------------------------------------
# Table: measured > cached-on-disk > modeled
# ---------------------------------------------------------------------------

_TABLE: dict[tuple, tuple] = {}
_DISK_LOADED = False


def _load_disk_cache() -> None:
    global _DISK_LOADED
    if _DISK_LOADED:
        return
    _DISK_LOADED = True
    path = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if not path or not os.path.exists(path):
        return
    try:
        with open(path) as fh:
            for k, v in json.load(fh).items():
                _TABLE[tuple(k.split("|"))] = tuple(v)
    except (OSError, ValueError):
        pass


def _save_disk_cache() -> None:
    path = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if not path:
        return
    try:
        with open(path, "w") as fh:
            json.dump({"|".join(map(str, k)): list(v)
                       for k, v in _TABLE.items()}, fh, indent=1)
    except OSError:
        pass


def _bucket(n: int) -> int:
    """Shape bucket: next power of two — keeps the table small and stops
    jit-cache-miss churn from ±1 ragged lengths."""
    return next_pow2(n)


def clear_table() -> None:
    """Drop all cached entries (tests / re-tuning)."""
    global _DISK_LOADED
    _TABLE.clear()
    _DISK_LOADED = False


def attention_params(p: int, m: int, e: int, f: int, *,
                     backend: str = "cpu",
                     impl: str = "jnp") -> AttentionParams:
    """Pick (block_q, block_k) for a prefill-shaped attention call."""
    _load_disk_cache()
    # model from the bucketed shape, not the exact one: every shape in a
    # bucket must resolve to the same tiles regardless of which caller
    # seeds the table entry first (stable jit keys / XLA-cache hits)
    pb, mb = _bucket(p), _bucket(m)
    key = ("attn", backend, impl, str(pb), str(mb), str(e), str(f))
    hit = _TABLE.get(key)
    if hit is not None:
        return AttentionParams(int(hit[0]), int(hit[1]))
    cands = _attention_candidates(pb, mb)
    best = min(cands, key=lambda c: _attention_cost(c, pb, mb, e, f))
    _TABLE[key] = (best.block_q, best.block_k)
    return best


def _paged_decode_candidates(n_pages: int, page_size: int) -> list[DecodeParams]:
    """Candidates for the paged split-K decode: ``splits`` must divide the
    page count (split boundaries stay page-aligned so the block-table
    lookup never straddles two pages) and ``block_k`` must divide
    ``page_size`` (one K/V tile is always a slice of a single page)."""
    base = _ARCH.pe2d_cols
    out = []
    for splits in (1, 2, 4, 8, 16):
        if splits > n_pages or n_pages % splits:
            continue
        split_tokens = (n_pages // splits) * page_size
        if split_tokens < base and splits > 1:
            continue
        for bk in (base, 2 * base, 4 * base):
            bk = min(bk, page_size)
            if page_size % bk:
                bk = page_size
            out.append(DecodeParams(splits, bk))
    return list(dict.fromkeys(out)) or [DecodeParams(1, page_size)]


def paged_decode_params(n_pages: int, page_size: int, g: int, e: int, f: int,
                        *, backend: str = "cpu",
                        impl: str = "jnp",
                        elem_bytes: int = 4) -> DecodeParams:
    """Pick (splits, block_k) for a paged split-K decode over ``n_pages``
    pages of ``page_size`` tokens each.  Same cost model as
    :func:`decode_params` (total M = n_pages·page_size) restricted to
    page-aligned candidates.

    ``elem_bytes`` is the page-pool element width: quantized pools
    (fp8/int8, 1 byte) halve-to-quarter the VMEM working set per tile, so
    the model may pick wider ``block_k`` tiles than for bf16/fp32 pools —
    keyed separately so both coexist in one process."""
    _load_disk_cache()
    key = ("pdecode", backend, impl, str(n_pages), str(page_size),
           str(_bucket(g)), str(e), str(f), str(elem_bytes))
    hit = _TABLE.get(key)
    if hit is not None:
        return DecodeParams(int(hit[0]), int(hit[1]))
    m = n_pages * page_size
    cands = _paged_decode_candidates(n_pages, page_size)
    best = min(cands,
               key=lambda c: _decode_cost(c, m, g, e, f,
                                          elem_bytes=elem_bytes))
    _TABLE[key] = (best.splits, best.block_k)
    return best


def mla_paged_decode_params(n_pages: int, page_size: int, g: int,
                            rank: int, rope_dim: int, *,
                            backend: str = "cpu",
                            impl: str = "jnp",
                            elem_bytes: int = 4) -> DecodeParams:
    """Pick (splits, block_k) for the paged *latent-space* MLA decode
    kernel: the K stream is the concatenated (rank + rope_dim) latent page
    pair and the V stream is the rank-wide latent itself, so the cost model
    runs with e = rank + rope_dim, f = rank over the same page-aligned
    candidate set as :func:`paged_decode_params` (splits divide the table
    width, block_k divides page_size).  ``elem_bytes`` as in
    :func:`paged_decode_params` (quantized latent pools)."""
    _load_disk_cache()
    key = ("mla-pdecode", backend, impl, str(n_pages), str(page_size),
           str(_bucket(g)), str(rank), str(rope_dim), str(elem_bytes))
    hit = _TABLE.get(key)
    if hit is not None:
        return DecodeParams(int(hit[0]), int(hit[1]))
    m = n_pages * page_size
    cands = _paged_decode_candidates(n_pages, page_size)
    best = min(cands,
               key=lambda c: _decode_cost(c, m, g, rank + rope_dim, rank,
                                          elem_bytes=elem_bytes))
    _TABLE[key] = (best.splits, best.block_k)
    return best


def verify_block_k(block_k: int, *, p: int, g: int, e: int, f: int,
                   elem_bytes: int = 4) -> int:
    """VMEM sanity-clamp for the speculative *verify* dispatch.

    Verify reuses the split geometry tuned for single-token decode (the
    autotune key never sees P — that is what keeps per-position outputs
    bit-identical to non-speculative decode), but the q tile and the
    running-state scratch grow p-fold (p positions × g rows).  Halve
    ``block_k`` until the grown working set fits ``VMEM_BUDGET`` —
    halving preserves the wrappers' divisibility contracts (block_k
    divides split_len / page_size, both powers-of-two-multiples).
    ``splits`` is never touched: the split count shapes the associative
    combine, block_k only tiles the sequential sweep."""
    if p <= 1:
        return block_k
    rows = p * g
    base = _ARCH.pe2d_cols
    while block_k > base:
        vmem = (rows * e + block_k * (e + f) + rows * f
                + 2 * rows * 128) * elem_bytes
        if vmem <= VMEM_BUDGET:
            break
        block_k //= 2
    return block_k


def decode_params(m: int, g: int, e: int, f: int, *,
                  backend: str = "cpu",
                  impl: str = "jnp") -> DecodeParams:
    """Pick (splits, block_k) for a split-K decode against an M-slot cache.

    Keyed by the *exact* cache length: splits/block_k validity depends on
    M's divisors, so bucket-sharing entries across lengths (as the
    attention table does) could hand one shape another's infeasible tile.
    Cache lengths are fixed per engine (max_len), so the table stays small.
    """
    _load_disk_cache()
    key = ("decode", backend, impl, str(m), str(_bucket(g)),
           str(e), str(f))
    hit = _TABLE.get(key)
    if hit is not None:
        return DecodeParams(int(hit[0]), int(hit[1]))
    cands = _decode_candidates(m)
    best = min(cands, key=lambda c: _decode_cost(c, m, g, e, f))
    _TABLE[key] = (best.splits, best.block_k)
    return best


# ---------------------------------------------------------------------------
# Measured mode
# ---------------------------------------------------------------------------

def time_fn(fn: Callable, *args, iters: int = 5, warmup: int = 2) -> float:
    """Median wall-clock seconds per ``fn(*args)`` call: ``warmup`` untimed
    calls (jit compile + caches), then the median of ``iters`` timed calls,
    each synchronized with ``jax.block_until_ready`` so async dispatch
    doesn't lie.  The one timing protocol for the autotuner's measured mode
    and the benchmark harness."""
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)


def measure_best(
    make_fn: Callable[..., Callable],
    candidates: Sequence,
    *args,
    key: Optional[tuple] = None,
    iters: int = 5,
    warmup: int = 2,
):
    """Time each candidate (median of ``iters`` after ``warmup``) and return
    ``(best_candidate, {candidate: seconds})``.

    ``make_fn(candidate)`` must return a callable taking ``*args``; timing
    follows :func:`time_fn`.  A candidate that fails to build or run is
    skipped with a warning that names it and the error.  When ``key`` is
    given, the winner is written into the autotune table (and the on-disk
    cache if ``REPRO_AUTOTUNE_CACHE`` is set) so subsequent
    :func:`attention_params` / :func:`decode_params` lookups return it.
    """
    timings: dict = {}
    for cand in candidates:
        try:
            timings[cand] = time_fn(make_fn(cand), *args,
                                    iters=iters, warmup=warmup)
        except Exception as err:                 # infeasible candidate
            _log.warning("measure_best: skipping candidate %r: %s: %s",
                         cand, type(err).__name__, err)
            timings[cand] = float("inf")
    best = min(timings, key=timings.get)
    if timings[best] == float("inf"):
        raise RuntimeError(
            "measure_best: every candidate failed; nothing to return "
            f"(candidates={list(candidates)!r})")
    if key is not None:
        _TABLE[tuple(map(str, key))] = tuple(dataclasses.astuple(best))
        _save_disk_cache()
    return best, timings
