"""Dense decoder blocks: one run of identical pre-norm attention + gated
SiLU MLP layers.  Two configurations of the field take this form:

* LayerNorm, multi-head attention, gated SiLU MLP (StableLM-2);
* RMSNorm, grouped-query attention, gated SiLU MLP (Granite-3.0).

Both: pre-norm residual blocks, rotary embedding over the whole head
(rotate-half pairs, frequencies ``θ^(-i/half)``), causal softmax
attention scaled by ``1/sqrt(head_dim)`` with query head ``h`` reading kv
head ``h // (heads / kv_heads)``, norm weights 1 and biases 0 (their
initial values), epsilon 1e-6, and an untied or tied output head.

An architecture module is what ``bench/configs/<config>.json`` names
under ``"arch"``; ``bench/registry.py`` loads ``bench/arch/<arch>.py``
by that name.  Everything in the benchmark that knows a model's shape
asks it, through these names and no others:

``model_config(config)``   the program's ``ModelConfig`` (program side)
``kv_elements(config)``    K/V elements the engine's pool holds, as the
                           configuration describes it (``kv_bits``)
``Shapes.from_config(config)``  the work counts: ``decode_token_flops(ctx)``
                           and ``prefill_flops(start, end)`` of the whole
                           step (``model_mfu``, ``decode_mfu``), plus
                           whatever this architecture's kernel readers use
``KERNELS``                kernel -> (program class, Pallas kernel name)
                           (``bench/trace.py``)
``Arch.from_config(config)``  the reference's sizes, hashable, with ``d``,
                           ``vocab`` and ``norm`` (the final norm's kind)
``model_keys(seed, a)``    (embedding key, output-head key, one
                           ``(kind, key)`` per layer in order)
``layer_forward(a, kind, x, key, fp8)``  one layer of that kind over
                           ``x [B, T, d]``, its weights drawn from ``key``

The reference side (``Arch`` and below) imports nothing of the program
and draws every weight anew from the seed, following the program's init
arithmetic (``repro.model`` ``init``).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from bench.reference import draw, mm, norm, rope

#: one kind of layer: every layer of a dense stack is the same block
BLOCK = "block"

#: kernel -> (program class that runs it, the Pallas kernel's name)
KERNELS = {
    "paged_decode": ("decode", "fusemax_decode_paged"),
    "prefill_attention": ("prefill", "fusemax_attention"),
}


def _head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


# -- the program's side -------------------------------------------------------

def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=config.get("model_type", "bench"),
        n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        vocab=config["vocab_size"],
        head_dim=_head_dim(config),
        family="dense",
        norm=config["norm"],
        rope_theta=float(config["rope_theta"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        source=config["source"],
    )


def kv_elements(config: dict) -> int:
    """Pages x page size x layers x (K, V) x kv heads x head size."""
    e = config["engine"]
    return (e["num_pages"] * e["page_size"] * config["num_hidden_layers"]
            * 2 * config["num_key_value_heads"] * _head_dim(config))


# -- work counts (bench/work.py sets out what they count) ---------------------

@dataclasses.dataclass(frozen=True)
class Shapes:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    #: bytes per element of the served weights and K/V (bf16: 2)
    itemsize: int = 2

    @classmethod
    def from_config(cls, c: dict) -> "Shapes":
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=_head_dim(c),
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   itemsize={"bfloat16": 2, "float32": 4}[c["torch_dtype"]])

    @property
    def layer_matmul_params(self) -> int:
        """Weights one layer multiplies by: q, k, v, o and the gated MLP."""
        d, dh = self.d_model, self.head_dim
        attn = d * self.heads * dh + 2 * d * self.kv_heads * dh \
            + self.heads * dh * d
        return attn + 3 * d * self.d_ff

    @property
    def head_flops(self) -> int:
        """Unembedding of one position."""
        return 2 * self.d_model * self.vocab

    def attention_flops(self, keys: int) -> int:
        """One query position attending ``keys`` positions in one layer:
        q·kᵀ and p·v, each 2·keys·dh per head."""
        return 4 * keys * self.heads * self.head_dim

    def decode_token_flops(self, ctx: int) -> int:
        """One decoded token whose attention reads ``ctx`` positions
        (itself included), through every layer and the unembedding."""
        return self.layers * (2 * self.layer_matmul_params
                              + self.attention_flops(ctx)) + self.head_flops

    def prefill_flops(self, start: int, end: int) -> int:
        """Prompt positions ``[start, end)`` of one sequence, causal, with
        the logits of the last position only (the one the first token is
        sampled from).  Positions below ``start`` are cached and cost
        nothing here."""
        n = end - start
        if n <= 0:
            return 0
        keys = (start + 1 + end) * n // 2  # sum of (p + 1), p in [start, end)
        return self.layers * (2 * self.layer_matmul_params * n
                              + 4 * self.heads * self.head_dim * keys) \
            + self.head_flops

    def paged_decode_flops(self, ctx: int) -> int:
        """The paged decode attention kernel's share of one decoded token."""
        return self.layers * self.attention_flops(ctx)

    def paged_decode_bytes(self, ctx: int) -> int:
        """Bytes the paged decode attention kernel must move for one
        decoded token: the K and V of ``ctx`` positions per kv head, the
        query in and the output out, in every layer."""
        kv = 2 * ctx * self.kv_heads * self.head_dim
        qo = 2 * self.heads * self.head_dim
        return self.layers * (kv + qo) * self.itemsize


# -- the reference ------------------------------------------------------------

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
        return cls(layers=c["num_hidden_layers"], d=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=_head_dim(c),
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   norm=c["norm"], tied=bool(c["tie_word_embeddings"]),
                   rope_theta=float(c["rope_theta"]))


def layer_weights(a: Arch, key) -> dict:
    """One layer's weights from its key (``ks[0]`` attention, ``ks[1]``
    MLP; norms hold no draws)."""
    ks = jax.random.split(key, 8)
    ka = jax.random.split(ks[0], 4)
    km = jax.random.split(ks[1], 3)
    s_in = 1.0 / math.sqrt(a.d)
    return {
        "wq": draw(ka[0], (a.d, a.heads, a.head_dim), s_in),
        "wk": draw(ka[1], (a.d, a.kv_heads, a.head_dim), s_in),
        "wv": draw(ka[2], (a.d, a.kv_heads, a.head_dim), s_in),
        "wo": draw(ka[3], (a.heads, a.head_dim, a.d),
                   1.0 / math.sqrt(a.heads * a.head_dim)),
        "wg": draw(km[0], (a.d, a.d_ff), s_in),
        "wu": draw(km[1], (a.d, a.d_ff), s_in),
        "wd": draw(km[2], (a.d_ff, a.d), 1.0 / math.sqrt(a.d_ff)),
    }


def model_keys(seed: int, a: Arch):
    """(embedding key, output-head key, per-layer ``(kind, key)``).  One
    run of identical layers: the layer keys split ``fold_in(key, 1000)``."""
    key = jax.random.PRNGKey(seed)
    top = jax.random.split(key, 4)
    layers = jax.random.split(jax.random.fold_in(key, 1000), a.layers)
    return (top[0], (top[0] if a.tied else top[1]),
            [(BLOCK, layers[i]) for i in range(a.layers)])


def layer_forward(a: Arch, kind: str, x, key, fp8: bool = False):
    """One block over x [B, T, d], its weights drawn from ``key``."""
    if kind != BLOCK:
        raise ValueError(f"a dense stack has no layer kind {kind!r}")
    w = layer_weights(a, key)
    b, t, _ = x.shape
    h = norm(x, a.norm)
    q = rope(mm("btd,dhe->bhte", h, w["wq"], fp8), a.rope_theta)
    k = rope(mm("btd,dhe->bhte", h, w["wk"], fp8), a.rope_theta)
    v = mm("btd,dhe->bhte", h, w["wv"], fp8)
    g = a.heads // a.kv_heads
    q = q.reshape(b, a.kv_heads, g, t, a.head_dim)
    s = mm("bkgte,bkse->bkgts", q, k, fp8, -1, -1) / math.sqrt(a.head_dim)
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = mm("bkgts,bkse->bkgte", p, v, fp8, -1, -2)
    o = o.reshape(b, a.heads, t, a.head_dim)
    x = x + mm("bhte,hed->btd", o, w["wo"], fp8, (1, 3))
    h = norm(x, a.norm)
    f = jax.nn.silu(mm("btd,df->btf", h, w["wg"], fp8)) \
        * mm("btd,df->btf", h, w["wu"], fp8)
    return x + mm("btf,fd->btd", f, w["wd"], fp8)
