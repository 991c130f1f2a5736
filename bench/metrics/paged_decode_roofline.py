"""Kernels: the paged decode attention kernel's share of its roofline, in
%.  The least time the chip could take for the K/V of every decoded
token's live context plus its query and output (the dense
architecture's counts, ``bench/arch/dense.py``, and
``bench/work.py``: the larger of operations over peak bf16 rate and
bytes over peak HBM bandwidth, memory-bound here), over the kernel's
device time.  Nothing to read where the architecture's kernel table has
no ``paged_decode``.  Moves ``tpot_p95_ms``."""

from bench import work


def read(rec):
    t = rec["trace"].kernels.get("paged_decode")
    ctx = rec["decode_ctx"]
    if not t or not ctx:
        return None
    s = rec["shapes"]
    flops = sum(s.paged_decode_flops(c) for c in ctx)
    nbytes = sum(s.paged_decode_bytes(c) for c in ctx)
    return 100.0 * work.roofline_seconds(flops, nbytes, rec["peak"]) / t
