"""Pallas TPU kernels for the FuseMax attention mapping.

``fusemax.py``  — 1-pass fused attention (Cascade 5 → Mapping 1 on TPU)
``decode.py``   — split-K decode instantiation (ragged KV caches)
``ops.py``      — jit'd public wrappers (padding, GQA folding, dispatch,
                  differentiable custom-VJP jnp path for training/dry-run)
``autotune.py`` — per-(shape, backend) block-size / split selection
``ref.py``      — pure-jnp fp32 oracles
"""
from repro.kernels import autotune
from repro.kernels.autotune import (
    AttentionParams, DecodeParams, attention_params, decode_params,
    measure_best, mla_paged_decode_params, paged_decode_params,
)
from repro.kernels.fusemax import exp_maccs, fusemax_attention_pallas
from repro.kernels.decode import (
    fusemax_decode_paged_pallas, fusemax_decode_pallas,
    fusemax_mla_decode_paged_pallas,
)
from repro.kernels.ops import (
    fusemax_attention, fusemax_decode, fusemax_decode_paged,
    fusemax_mla_decode_paged, gather_kv_pages, gather_pages,
    mla_combine_partials,
    mla_decode_partials,
)
from repro.kernels.ref import decode_reference, mha_reference

__all__ = [
    "AttentionParams",
    "DecodeParams",
    "attention_params",
    "autotune",
    "decode_params",
    "decode_reference",
    "exp_maccs",
    "gather_kv_pages",
    "gather_pages",
    "measure_best",
    "mla_combine_partials",
    "mla_decode_partials",
    "mla_paged_decode_params",
    "paged_decode_params",
    "fusemax_attention",
    "fusemax_attention_pallas",
    "fusemax_decode",
    "fusemax_decode_paged",
    "fusemax_decode_paged_pallas",
    "fusemax_decode_pallas",
    "fusemax_mla_decode_paged",
    "fusemax_mla_decode_paged_pallas",
    "mha_reference",
]
