"""Model step, whole: operations the model needs for the tokens prefilled
and decoded in the traced window (the architecture's work counts,
``Shapes`` in ``bench/arch/<arch>.py``), over the window times the
chip's peak bf16 rate, in %.  Moves ``tok_per_s``."""


def read(rec):
    s, peak, red = rec["shapes"], rec["peak"], rec["trace"]
    flops = sum(s.decode_token_flops(c) for c in rec["decode_ctx"])
    flops += sum(s.prefill_flops(a, b) for a, b in rec["prefills"])
    if not flops:
        return None
    return 100.0 * flops / (red.window_s * peak["bf16_flops_per_s"])
