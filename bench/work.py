"""Operations and bytes the algorithm needs, from shapes and token counts.

Every per-layer share of a peak divides by these numbers.  They count the
work of the mathematics, not of one implementation: no lane padding, no
page rounding, no masked decode steps, no logits for prompt positions
whose logits nobody reads.  Any implementation is then measured against
the same denominator.

``Shapes`` is built from a configuration file (``bench/configs/*.json``);
a matmul of an ``[n, k]`` by ``[k, m]`` operand counts ``2·n·k·m``
operations.
"""
from __future__ import annotations

import dataclasses


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
        heads = c["num_attention_heads"]
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=heads, kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim") or c["hidden_size"] // heads,
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


def attention_flops(s: Shapes, keys: int) -> int:
    """One query position attending ``keys`` positions in one layer:
    q·kᵀ and p·v, each 2·keys·dh per head."""
    return 4 * keys * s.heads * s.head_dim


def decode_token_flops(s: Shapes, ctx: int) -> int:
    """One decoded token whose attention reads ``ctx`` positions
    (itself included), through every layer and the unembedding."""
    return s.layers * (2 * s.layer_matmul_params + attention_flops(s, ctx)) \
        + s.head_flops


def prefill_flops(s: Shapes, start: int, end: int) -> int:
    """Prompt positions ``[start, end)`` of one sequence, causal, with the
    logits of the last position only (the one the first token is sampled
    from).  Positions below ``start`` are cached and cost nothing here."""
    n = end - start
    if n <= 0:
        return 0
    keys = (start + 1 + end) * n // 2          # sum of (p + 1) over [start, end)
    return s.layers * (2 * s.layer_matmul_params * n
                       + 4 * s.heads * s.head_dim * keys) + s.head_flops


def paged_decode_flops(s: Shapes, ctx: int) -> int:
    """The paged decode attention kernel's share of one decoded token."""
    return s.layers * attention_flops(s, ctx)


def paged_decode_bytes(s: Shapes, ctx: int) -> int:
    """Bytes the paged decode attention kernel must move for one decoded
    token: the K and V of ``ctx`` positions per kv head, the query in and
    the output out, in every layer."""
    kv = 2 * ctx * s.kv_heads * s.head_dim
    qo = 2 * s.heads * s.head_dim
    return s.layers * (kv + qo) * s.itemsize


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """Least time the chip could take: the larger of the compute bound and
    the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
