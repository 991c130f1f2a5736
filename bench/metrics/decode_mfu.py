"""Model step, decode: operations the decoded tokens of the traced window
need (the architecture's work counts, ``Shapes`` in
``bench/arch/<arch>.py``), over the decode programs' device time times
the chip's peak bf16 rate, in %.  The whole decode step's share of the
peak, beside the paged kernel's roofline.  Moves ``tpot_p95_ms``."""


def read(rec):
    t = rec["trace"].programs.get("decode")
    ctx = rec["decode_ctx"]
    if not t or not ctx:
        return None
    s = rec["shapes"]
    flops = sum(s.decode_token_flops(c) for c in ctx)
    return 100.0 * flops / (t * rec["peak"]["bf16_flops_per_s"])
