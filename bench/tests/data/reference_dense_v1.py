# The float32 reference as bench/reference.py held it while it knew only
# dense blocks, kept verbatim below this comment: bench/tests/test_arch.py
# holds the architecture modules' reference to its logits, bit for bit.
"""The plain reference: a float32 ``jax.numpy`` forward of a configuration.

It imports nothing of the program under test and takes nothing the
program made.  It draws the weights anew from the seed, following the
program's published init arithmetic (``repro.model`` ``init``): the same
key splits, the same normal draws and scales, rounded to bfloat16 as the
program serves them and then held in float32.  It follows the program's
equations, departures included (each is listed in the configuration
file), for the two block types the benchmark runs:

* LayerNorm, multi-head attention, gated SiLU MLP (StableLM-2);
* RMSNorm, grouped-query attention, gated SiLU MLP (Granite-3.0).

Both: pre-norm residual blocks, rotary embedding over the whole head
(rotate-half pairs, frequencies ``θ^(-i/half)``), causal softmax
attention scaled by ``1/sqrt(head_dim)`` with query head ``h`` reading kv
head ``h // (heads / kv_heads)``, norm weights 1 and biases 0 (their
initial values), epsilon 1e-6, and an untied or tied output head.

Every matmul runs at ``Precision.HIGHEST``: on a TPU a float32 matmul is
otherwise done in bfloat16 passes.  It runs layer by layer, drawing one
layer's weights at a time inside the jitted layer, over sequences padded
to one length, so that one compiled program serves every call and only
one layer's weights are on the device at once.

``precision="fp8"`` is the control: the same forward with both operands
of every matmul rounded to float8 e4m3 (scaled per tensor for weights,
per row for activations), the nearest precision below the bfloat16 the
configuration states.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class Arch:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    norm: str                 # "layernorm" | "rmsnorm"
    tied: bool
    rope_theta: float

    @classmethod
    def from_config(cls, c: dict) -> "Arch":
        if c.get("partial_rotary_factor", 1.0) != 1.0:
            raise ValueError("the reference rotates the whole head")
        heads = c["num_attention_heads"]
        return cls(layers=c["num_hidden_layers"], d=c["hidden_size"],
                   heads=heads, kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim") or c["hidden_size"] // heads,
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   norm=c["norm"], tied=bool(c["tie_word_embeddings"]),
                   rope_theta=float(c["rope_theta"]))


# -- weights, drawn as the program draws them -------------------------------

def _draw(key, shape, scale):
    w = jax.random.normal(key, shape) * scale
    return w.astype(jnp.bfloat16).astype(jnp.float32)


def layer_weights(a: Arch, key) -> dict:
    """One layer's weights from its key (``ks[0]`` attention, ``ks[1]``
    MLP; norms hold no draws)."""
    ks = jax.random.split(key, 8)
    ka = jax.random.split(ks[0], 4)
    km = jax.random.split(ks[1], 3)
    s_in = 1.0 / math.sqrt(a.d)
    return {
        "wq": _draw(ka[0], (a.d, a.heads, a.head_dim), s_in),
        "wk": _draw(ka[1], (a.d, a.kv_heads, a.head_dim), s_in),
        "wv": _draw(ka[2], (a.d, a.kv_heads, a.head_dim), s_in),
        "wo": _draw(ka[3], (a.heads, a.head_dim, a.d),
                    1.0 / math.sqrt(a.heads * a.head_dim)),
        "wg": _draw(km[0], (a.d, a.d_ff), s_in),
        "wu": _draw(km[1], (a.d, a.d_ff), s_in),
        "wd": _draw(km[2], (a.d_ff, a.d), 1.0 / math.sqrt(a.d_ff)),
    }


def model_keys(seed: int, a: Arch):
    """(embedding key, output-head key, per-layer keys).  One run of
    identical layers: the layer keys split ``fold_in(key, 1000)``."""
    key = jax.random.PRNGKey(seed)
    top = jax.random.split(key, 4)
    layers = jax.random.split(jax.random.fold_in(key, 1000), a.layers)
    return top[0], (top[0] if a.tied else top[1]), layers


# -- the forward ----------------------------------------------------------

def _q8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    f8 = jnp.float8_e4m3fn
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / s).astype(f8).astype(jnp.float32) * s


def _mm(spec, x, w, fp8: bool, x_axis=-1, w_axis=None):
    if fp8:
        x = _q8(x, x_axis)
        w = _q8(w, w_axis)
    return jnp.einsum(spec, x, w, precision=HIGHEST)


def _norm(x, kind):
    if kind == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + EPS)


def _rope(x, theta):
    """x [B, H, T, E] at positions 0..T-1 (rotate-half pairs)."""
    t, e = x.shape[-2], x.shape[-1]
    half = e // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer_forward(a: Arch, x, key, fp8: bool = False):
    """One block over x [B, T, d], its weights drawn from ``key``."""
    w = layer_weights(a, key)
    b, t, _ = x.shape
    h = _norm(x, a.norm)
    q = _rope(_mm("btd,dhe->bhte", h, w["wq"], fp8), a.rope_theta)
    k = _rope(_mm("btd,dhe->bhte", h, w["wk"], fp8), a.rope_theta)
    v = _mm("btd,dhe->bhte", h, w["wv"], fp8)
    g = a.heads // a.kv_heads
    q = q.reshape(b, a.kv_heads, g, t, a.head_dim)
    s = _mm("bkgte,bkse->bkgts", q, k, fp8, -1, -1) \
        / math.sqrt(a.head_dim)
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _mm("bkgts,bkse->bkgte", p, v, fp8, -1, -2)
    o = o.reshape(b, a.heads, t, a.head_dim)
    x = x + _mm("bhte,hed->btd", o, w["wo"], fp8, (1, 3))
    h = _norm(x, a.norm)
    f = jax.nn.silu(_mm("btd,df->btf", h, w["wg"], fp8)) \
        * _mm("btd,df->btf", h, w["wu"], fp8)
    return x + _mm("btf,fd->btd", f, w["wd"], fp8)


@functools.lru_cache(maxsize=None)
def _jitted(a: Arch, fp8: bool):
    embed = jax.jit(lambda key, tokens: _draw(
        key, (a.vocab, a.d), 1.0)[tokens])
    layer = jax.jit(functools.partial(layer_forward, a, fp8=fp8))
    final = jax.jit(lambda x: _norm(x, a.norm))

    def head(key, xs):
        table = _draw(key, (a.vocab, a.d), 1.0)
        return _mm("nd,vd->nv", xs, table, fp8)

    return embed, layer, final, jax.jit(head)


def logits_at(config: dict, seed: int, seqs: Sequence[np.ndarray],
              positions: Sequence[np.ndarray], pad_to: int,
              precision: str = "f32", block: int = 4) -> List[np.ndarray]:
    """Reference logits of sequence ``i`` at ``positions[i]`` (each a
    position whose next token is predicted), every sequence run
    teacher-forced from its first token.  Sequences go through in blocks
    of ``block`` rows, each padded at the end to ``pad_to`` tokens
    (causal attention keeps the padding out of every real position), so
    that one compiled program serves every block and the activations of
    one block are on the device at once."""
    a = Arch.from_config(config)
    embed, layer, final, head = _jitted(a, precision == "fp8")
    k_embed, k_head, k_layers = model_keys(seed, a)
    out: List[np.ndarray] = []
    for b0 in range(0, len(seqs), block):
        chunk = list(range(b0, min(b0 + block, len(seqs))))
        toks = np.zeros((block, pad_to), np.int32)
        for r, i in enumerate(chunk):
            toks[r, :len(seqs[i])] = seqs[i]
        x = embed(k_embed, jnp.asarray(toks))
        for l in range(a.layers):
            x = layer(x, k_layers[l])
        x = final(x)
        rows = np.concatenate([np.full(len(positions[i]), r)
                               for r, i in enumerate(chunk)])
        cols = np.concatenate([np.asarray(positions[i]) for i in chunk])
        got = np.asarray(head(k_head, x[rows, cols]))
        out.extend(np.split(got, np.cumsum(
            [len(positions[i]) for i in chunk])[:-1]))
    return out


def served_gaps(ref: np.ndarray, served: np.ndarray) -> np.ndarray:
    """How far each served token's reference logit lies below the
    reference's best at that position."""
    return ref.max(-1) - ref[np.arange(len(served)), served]


def teacher_forced(prompt: np.ndarray, generated: Sequence[int]
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The sequence a request's tokens were served from, the positions
    whose logits chose each served token, and the served tokens."""
    gen = np.asarray(generated, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), gen[:-1]])
    pos = np.arange(len(prompt) - 1, len(prompt) - 1 + len(gen))
    return seq, pos, gen
