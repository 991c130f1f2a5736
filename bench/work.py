"""What the work counts count, and the roofline they are held against.

Every per-layer share of a peak divides by operations and bytes that the
architecture module of the configuration counts from shapes and token
counts (``Shapes`` in ``bench/arch/<arch>.py``).  They count the work of
the mathematics, not of one implementation: no lane padding, no page
rounding, no masked decode steps, no logits for prompt positions whose
logits nobody reads.  Any implementation is then measured against the
same denominator.  A matmul of an ``[n, k]`` by ``[k, m]`` operand counts
``2·n·k·m`` operations.
"""
from __future__ import annotations


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """Least time the chip could take: the larger of the compute bound and
    the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
