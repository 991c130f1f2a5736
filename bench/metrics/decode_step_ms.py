"""Model step: device time of the fused decode programs in the trace,
per decode step they ran.  Moves ``tpot_p95_ms``."""


def read(rec):
    t = rec["trace"].programs.get("decode")
    steps = rec["stats"]["decode_steps"]
    if not t or not steps:
        return None
    return t * 1e3 / steps
