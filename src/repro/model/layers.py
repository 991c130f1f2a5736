"""Primitive layers: params-as-pytrees with logical sharding axes.

No NN library is used — parameters are nested dicts of arrays, and every
init function returns ``(params, axes)`` where ``axes`` mirrors ``params``
with a tuple of *logical axis names* per array (MaxText-style).  The
distributed layer (:mod:`repro.distributed.sharding`) maps logical names →
mesh axes; models never mention the mesh.

Logical axis vocabulary:
  "embed"    d_model                     → usually sharded over TP ("model")
  "heads"    attention heads             → TP
  "kv_heads" kv heads                    → TP
  "head_dim" per-head dim                → replicated
  "mlp"      FFN hidden                  → TP
  "vocab"    vocabulary                  → TP
  "experts"  MoE expert count            → EP (model axis)
  "latent"   MLA latent / LoRA ranks     → replicated
  "state"    SSM state dim               → replicated
  None       replicated scalar-ish dims
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp

Params = Any      # nested dict of arrays
Axes = Any        # nested dict of tuples (mirrors Params)


@dataclasses.dataclass(frozen=True)
class Runtime:
    """Execution knobs threaded through forward passes (not config)."""

    #: "jnp" | "pallas" | "ref" | "auto" (Pallas on TPU, jnp elsewhere)
    attn_impl: str = "jnp"
    exp_impl: str = "native"    # "native" | "maccs"
    #: kernel tile sizes; None → per-(shape, backend) autotuner defaults
    #: (repro.kernels.autotune)
    block_q: Optional[int] = None
    block_k: Optional[int] = None
    interpret: Optional[bool] = None
    param_dtype: Any = jnp.float32
    activation_dtype: Any = jnp.bfloat16
    #: unroll scanned layer runs (dry-run: makes cost_analysis FLOPs exact)
    unroll_runs: bool = False
    #: split-K factor for decode; None → autotuned (align with the
    #: model-axis size when the KV cache is sequence-sharded →
    #: distributed split-K decode)
    decode_splits: Optional[int] = None
    # activation-sharding hook installed by the distributed layer; takes
    # (x, logical_axes) and returns x (identity by default).
    shard_activation: Callable = staticmethod(lambda x, axes: x)
    #: paged-pool device sharding (repro.distributed.sharding.KVShard):
    #: page arrays split along the kv-head / latent-rank axis and the
    #: paged attention ops run under shard_map.  None → single-device
    #: pool (every non-paged path ignores this).
    kv_shard: Optional[Any] = None


def _init(key, shape, scale, dtype):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Dense / embedding
# ---------------------------------------------------------------------------

def dense_init(key, in_dim: int, out_shape: Sequence[int], axes: Sequence,
               dtype=jnp.float32, scale: Optional[float] = None):
    """Weight [in_dim, *out_shape]; fan-in init."""
    shape = (in_dim, *out_shape)
    scale = scale if scale is not None else 1.0 / math.sqrt(in_dim)
    return {"w": _init(key, shape, scale, dtype)}, {"w": tuple(axes)}


def dense(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """x [..., in] @ w [in, *out] → [..., *out], contracting one axis."""
    w = p["w"].astype(x.dtype)
    n_out = w.ndim - 1
    return jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=x.dtype,
    ) if n_out == 1 else jnp.tensordot(x, w, axes=((x.ndim - 1,), (0,)))


def embedding_init(key, vocab: int, dim: int, dtype=jnp.float32):
    return (
        {"table": _init(key, (vocab, dim), 1.0, dtype)},
        {"table": ("vocab", "embed")},
    )


def embed(p: Params, tokens: jnp.ndarray, dtype) -> jnp.ndarray:
    return p["table"].astype(dtype)[tokens]


def unembed(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """Tied LM head: logits = x @ table.T."""
    return jnp.einsum("...d,vd->...v", x, p["table"].astype(x.dtype))


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------

def norm_init(dim: int, kind: str = "rmsnorm", dtype=jnp.float32):
    p = {"scale": jnp.zeros((dim,), dtype)}   # gemma-style (1 + scale)
    a = {"scale": ("embed",)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((dim,), dtype)
        a["bias"] = ("embed",)
    return p, a


def apply_norm(p: Params, x: jnp.ndarray, kind: str = "rmsnorm",
               eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps)
    elif kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
    else:
        raise ValueError(kind)
    y = y * (1.0 + p["scale"].astype(jnp.float32))
    if "bias" in p:
        y = y + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------

def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float = 10000.0,
         rope_dim: Optional[int] = None) -> jnp.ndarray:
    """Apply RoPE to the last dim of x [..., T, D] at ``positions`` [..., T].

    If ``rope_dim`` < D, only the leading ``rope_dim`` features rotate
    (decoupled-RoPE style); the remainder passes through.
    """
    d = x.shape[-1]
    rd = d if rope_dim is None else rope_dim
    if rd == 0:
        return x
    rot, rest = x[..., :rd], x[..., rd:]
    half = rd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs      # [..., T, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = rot[..., :half], rot[..., half:]
    cos = cos.astype(x.dtype)
    sin = sin.astype(x.dtype)
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([rotated, rest], axis=-1) if rd < d else rotated


# ---------------------------------------------------------------------------
# Gated MLP (SwiGLU / GeGLU / ReLU²)
# ---------------------------------------------------------------------------

_ACTS = {
    "silu": jax.nn.silu,
    "gelu": lambda x: jax.nn.gelu(x, approximate=True),
    "relu": jax.nn.relu,
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),
}


def mlp_init(key, d_model: int, d_ff: int, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(d_ff)
    return (
        {
            "wi_gate": _init(k1, (d_model, d_ff), s_in, dtype),
            "wi_up": _init(k2, (d_model, d_ff), s_in, dtype),
            "wo": _init(k3, (d_ff, d_model), s_out, dtype),
        },
        {
            "wi_gate": ("embed", "mlp"),
            "wi_up": ("embed", "mlp"),
            "wo": ("mlp", "embed"),
        },
    )


def mlp(p: Params, x: jnp.ndarray, act: str = "silu",
        rt: Runtime = Runtime()) -> jnp.ndarray:
    h = _ACTS[act](x @ p["wi_gate"].astype(x.dtype))
    h = h * (x @ p["wi_up"].astype(x.dtype))
    h = rt.shard_activation(h, ("batch", "seq", "mlp"))
    return h @ p["wo"].astype(x.dtype)


def softcap(x: jnp.ndarray, cap: Optional[float]) -> jnp.ndarray:
    return x if cap is None else cap * jnp.tanh(x / cap)
