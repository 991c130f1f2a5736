"""The plain reference: a float32 ``jax.numpy`` forward of a configuration.

It imports nothing of the program under test and takes nothing the
program made.  It draws the weights anew from the seed, following the
program's published init arithmetic (``repro.model`` ``init``): the same
key splits, the same normal draws and scales, rounded to bfloat16 as the
program serves them and then held in float32.  It follows the program's
equations, departures included (each is listed in the configuration
file).  What is common to every architecture is here: the embedding, the
final norm, the output head, the loop over layers and blocks of rows, and
the primitives below.  The layers themselves, their keys and their sizes
are the architecture module's (``bench/arch/<arch>.py``).

Every matmul runs at ``Precision.HIGHEST``: on a TPU a float32 matmul is
otherwise done in bfloat16 passes.  It runs layer by layer, drawing one
layer's weights at a time inside the jitted layer, over sequences padded
to one length, so that one compiled program per kind of layer serves
every call and only one layer's weights are on the device at once.

``precision="fp8"`` is the control: the same forward with both operands
of every matmul rounded to float8 e4m3 (scaled per tensor for weights,
per row for activations), the nearest precision below the bfloat16 the
configuration states.
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-6


# -- primitives, shared by the architecture modules ---------------------------

def draw(key, shape, scale):
    """A weight drawn as the program draws it: normal, scaled, rounded to
    bfloat16 as served, then held in float32."""
    w = jax.random.normal(key, shape) * scale
    return w.astype(jnp.bfloat16).astype(jnp.float32)


def _q8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    f8 = jnp.float8_e4m3fn
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / s).astype(f8).astype(jnp.float32) * s


def mm(spec, x, w, fp8: bool, x_axis=-1, w_axis=None):
    """``einsum`` at full float32 precision; with ``fp8`` both operands
    are first rounded to float8 (the control)."""
    if fp8:
        x = _q8(x, x_axis)
        w = _q8(w, w_axis)
    return jnp.einsum(spec, x, w, precision=HIGHEST)


def norm(x, kind):
    """RMSNorm or LayerNorm over the last axis, weight 1 and bias 0."""
    if kind == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + EPS)


def rope(x, theta):
    """x [B, H, T, E] at positions 0..T-1 (rotate-half pairs)."""
    t, e = x.shape[-2], x.shape[-1]
    half = e // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# -- the forward --------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _ends(a, fp8: bool):
    """The embedding, the final norm and the output head of ``a``."""
    embed = jax.jit(lambda key, tokens: draw(
        key, (a.vocab, a.d), 1.0)[tokens])
    final = jax.jit(lambda x: norm(x, a.norm))

    def head(key, xs):
        table = draw(key, (a.vocab, a.d), 1.0)
        return mm("nd,vd->nv", xs, table, fp8)

    return embed, final, jax.jit(head)


@functools.lru_cache(maxsize=None)
def _layer(arch, a, kind: str, fp8: bool):
    """One compiled program per kind of layer."""
    return jax.jit(functools.partial(arch.layer_forward, a, kind, fp8=fp8))


def logits_at(arch, config: dict, seed: int, seqs: Sequence[np.ndarray],
              positions: Sequence[np.ndarray], pad_to: int,
              precision: str = "f32", block: int = 4) -> List[np.ndarray]:
    """Reference logits of sequence ``i`` at ``positions[i]`` (each a
    position whose next token is predicted), every sequence run
    teacher-forced from its first token, through the layers of the
    architecture module ``arch``.  Sequences go through in blocks of
    ``block`` rows, each padded at the end to ``pad_to`` tokens (causal
    attention keeps the padding out of every real position), so that one
    compiled program per kind of layer serves every block and the
    activations of one block are on the device at once."""
    a = arch.Arch.from_config(config)
    fp8 = precision == "fp8"
    embed, final, head = _ends(a, fp8)
    k_embed, k_head, layers = arch.model_keys(seed, a)
    out: List[np.ndarray] = []
    for b0 in range(0, len(seqs), block):
        chunk = list(range(b0, min(b0 + block, len(seqs))))
        toks = np.zeros((block, pad_to), np.int32)
        for r, i in enumerate(chunk):
            toks[r, :len(seqs[i])] = seqs[i]
        x = embed(k_embed, jnp.asarray(toks))
        for kind, key in layers:
            x = _layer(arch, a, kind, fp8)(x, key)
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
