"""FuseMax 1-pass attention as a Pallas TPU kernel (paper §V).

TPU-native realization of Mapping 1 / Cascade 5:

  * Grid ``(B·Hkv, P1, M1)`` — M1 innermost ("arbitrary" / sequential):
    the iterative rank of Cascade 5.  P1 and the batch·head dim are
    "parallel" (independent output tiles → multiple TensorCores).
  * BlockSpec VMEM tiles: Q ``(block_q, E)`` stays resident across the M1
    sweep (output-stationary); K/V ``(block_k, E/F)`` stream per M1 step —
    Pallas double-buffers these HBM→VMEM fetches automatically, which is
    the TPU equivalent of the paper's epoch-pipelined fills (Fig. 4).
  * Running max / denominator / numerator·V (RM/RD/RNV, Eqs. 39-41) are
    fp32 VMEM scratch accumulators that persist across the M1 grid
    dimension — the paper's per-PE running state.
  * Both matmuls of one M1 step (BQK, Eq. 42; SLNV, Eq. 47) live in one
    kernel body, so the MXU alternates them exactly like the paper's
    cycle-interleaved ``BQK | SLNV`` (Fig. 5) while the VPU computes the
    correction Einsums (Eqs. 43-46, 48-52) — the paper's 1D-array work.
  * Division is deferred to the final M1 iteration (Eq. 53, §IV-D):
    F·P divisions instead of M·P.
  * ``exp_impl="maccs"`` evaluates exp with 6 multiply-accumulates
    (range-reduced 2^f Taylor/Horner) per the paper's [36] — no
    transcendental unit needed; ``"native"`` uses the VPU transcendental.

The kernel's VMEM working set is O(block_q·E + block_k·(E+F) + block_q·F):
**independent of sequence length M** — the paper's headline property.

Sequence-length padding, GQA head folding and dtype handling live in
:mod:`repro.kernels.ops`; the pure-jnp oracle is :mod:`repro.kernels.ref`.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.einsum import Cascade
from repro.core.taxonomy import attention_1pass

NEG_INF = -1e30


def prefill_cascade() -> Cascade:
    """Declared cascade of this kernel family (checked by the analyzer).

    The kernel below is Mapping 1 of Cascade 5: M1 is the sequential grid
    dimension (the cascade's iterative rank), the RM/RD/RNV scratch
    accumulators are the running state of Eqs. 39-41, and each K/V tile is
    visited exactly once — the structural lint
    (:mod:`repro.analysis.lint`) verifies all three properties against the
    actual ``pallas_call`` geometry.
    """
    return attention_1pass()
LANES = 128          # TPU lane width: scratch kept (block_q, LANES)
LOG2E = 1.4426950408889634

# Taylor coefficients of 2^f = exp(f·ln2) on f ∈ [0, 1): ln2^k / k!.
# Six multiply-accumulates via Horner — the paper's exp-on-the-MACC-array
# trick ([36]); max rel. error ≈ 1.4e-5 on [0,1).
_EXP2_COEFFS = (
    1.0,
    0.6931471805599453,
    0.24022650695910072,
    0.05550410866482158,
    0.009618129107628477,
    0.0013333558146428443,
    0.00015403530393381608,
)


def exp_maccs(x: jnp.ndarray) -> jnp.ndarray:
    """exp(x) for x ≤ 0 with 6 MACCs: exp(x) = 2^n · 2^f, t = x·log2e = n+f.

    2^n is assembled by integer exponent-field construction (free on the
    paper's PEs — a shift; on TPU a bitcast), 2^f by a 6-step Horner chain.
    """
    t = jnp.maximum(x * LOG2E, -126.0)
    n = jnp.floor(t)
    f = t - n
    p = jnp.full_like(f, _EXP2_COEFFS[6])
    for c in _EXP2_COEFFS[5::-1]:
        p = p * f + c                                    # 6 MACCs total
    two_n = jax.lax.bitcast_convert_type(
        (n.astype(jnp.int32) + 127) << 23, jnp.float32
    ).astype(x.dtype)
    return p * two_n


def _exp(x: jnp.ndarray, impl: str) -> jnp.ndarray:
    return exp_maccs(x) if impl == "maccs" else jnp.exp(x)


def _fusemax_kernel(
    q_ref, k_ref, v_ref,            # VMEM tiles
    o_ref,                          # output tile
    m_scratch, l_scratch, acc_scratch,
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    softcap: Optional[float],
    q_offset: int,
    group: int,
    block_q: int,
    block_k: int,
    m1_total: int,
    m_valid: int,
    exp_impl: str,
):
    p1 = pl.program_id(1)
    m1 = pl.program_id(2)

    @pl.when(m1 == 0)
    def _init():                                         # Eqs. 39-41
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    # ---- block-level skip: fully-masked (q-tile, k-tile) pairs ----------
    # qpos of folded rows r = p·group + g  →  position r // group.
    q_lo = (p1 * block_q) // group + q_offset
    q_hi = (p1 * block_q + block_q - 1) // group + q_offset
    k_lo = m1 * block_k
    k_hi = m1 * block_k + block_k - 1
    run = k_lo < m_valid
    if causal:
        run &= k_lo <= q_hi
    if window is not None:
        run &= k_hi > q_lo - window

    @pl.when(run)
    def _body():
        q_tile = q_ref[0].astype(jnp.float32)            # [block_q, E]
        k_tile = k_ref[0].astype(jnp.float32)            # [block_k, E]
        v_tile = v_ref[0].astype(jnp.float32)            # [block_k, F]

        # BQK (Eq. 42) — MXU
        s = jax.lax.dot_general(
            q_tile, k_tile, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                        # [block_q, block_k]
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)

        rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        qpos = (p1 * block_q + rows) // group + q_offset
        kpos = m1 * block_k + cols
        ok = kpos < m_valid                              # M padding
        if causal:
            ok &= kpos <= qpos
        if window is not None:
            ok &= kpos > qpos - window
        s = jnp.where(ok, s, NEG_INF)

        # LM / RM (Eqs. 43-44) — VPU
        m_prev = m_scratch[:, :1]                        # [block_q, 1]
        lm = jnp.max(s, axis=1, keepdims=True)           # local max
        m_new = jnp.maximum(m_prev, lm)                  # running max
        # SLN (Eq. 45) — exp on the MACC datapath when exp_impl="maccs"
        p = _exp(s - m_new, exp_impl)                    # [block_q, block_k]
        sld = jnp.sum(p, axis=1, keepdims=True)          # SLD (Eq. 46)
        # PRM / SPD / RD (Eqs. 48-50)
        prm = _exp(m_prev - m_new, exp_impl)             # correction factor
        l_prev = l_scratch[:, :1]
        l_new = l_prev * prm + sld
        # SLNV (Eq. 47) — second MXU op, interleaved with BQK per M1 step
        slnv = jax.lax.dot_general(
            p, v_tile, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                # [block_q, F]
        # SPNV / RNV (Eqs. 51-52)
        acc_scratch[...] = acc_scratch[...] * prm + slnv

        m_scratch[...] = jnp.broadcast_to(m_new, m_scratch.shape)
        l_scratch[...] = jnp.broadcast_to(l_new, l_scratch.shape)

    @pl.when(m1 == m1_total - 1)
    def _finish():                                       # AV (Eq. 53)
        l = l_scratch[:, :1]
        # fully-masked rows (padding) have l = 0 only if no block ran;
        # guard the division so padded rows emit 0, not NaN.
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scratch[...] / l).astype(o_ref.dtype)


def fusemax_attention_pallas(
    q: jnp.ndarray,   # [BHkv, PG, E]   (batch·kv-head folded, q-group folded)
    k: jnp.ndarray,   # [BHkv, Mp, E]
    v: jnp.ndarray,   # [BHkv, Mp, F]
    *,
    scale: float,
    causal: bool = False,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    q_offset: int = 0,
    group: int = 1,
    block_q: int = 128,
    block_k: int = 128,
    m_valid: Optional[int] = None,
    exp_impl: str = "native",
    interpret: bool = False,
) -> jnp.ndarray:
    """Raw pallas_call wrapper. Shapes must already be block-aligned
    (see :func:`repro.kernels.ops.fusemax_attention` for the public API).

    Query-side padding (PG rounded up to ``block_q``) needs no kernel-side
    validity bound: padded rows are < one tile, their logits are fully
    masked by ``m_valid``/causal masks only when real, and the caller
    slices ``[:, :pg]`` — so no ``p_valid`` parameter exists.
    """
    bh, pg, e = q.shape
    _, mp, f = v.shape
    if pg % block_q or mp % block_k:
        raise ValueError(f"unaligned: PG={pg}%{block_q}, M={mp}%{block_k}")
    m_valid = mp if m_valid is None else m_valid
    grid = (bh, pg // block_q, mp // block_k)

    kernel = functools.partial(
        _fusemax_kernel,
        scale=scale,
        causal=causal,
        window=window,
        softcap=softcap,
        q_offset=q_offset,
        group=group,
        block_q=block_q,
        block_k=block_k,
        m1_total=grid[2],
        m_valid=m_valid,
        exp_impl=exp_impl,
    )

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, e), lambda b, p1, m1: (b, p1, 0)),
            pl.BlockSpec((1, block_k, e), lambda b, p1, m1: (b, m1, 0)),
            pl.BlockSpec((1, block_k, f), lambda b, p1, m1: (b, m1, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, f), lambda b, p1, m1: (b, p1, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, pg, f), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),   # RM
            pltpu.VMEM((block_q, LANES), jnp.float32),   # RD
            pltpu.VMEM((block_q, f), jnp.float32),       # RNV
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name="fusemax_attention",
        interpret=interpret,
    )(q, k, v)
