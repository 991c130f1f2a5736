"""Device: what the compiled fused decode program asks of HBM, arguments
plus temporaries, as the compiler's ``memory_analysis`` reports it, in
GiB.  Moves ``tok_per_s`` (the pool it leaves room for sets the batch)."""


def read(rec):
    b = rec["decode_hbm_bytes"]
    return b / 2**30 if b else None
