"""Device: share of the traced window in which no operation ran on the
chip (1 - union of the op intervals / window).  Moves ``tok_per_s``."""


def read(rec):
    red = rec["trace"]
    return red.idle_frac if red.window_s > 0 else None
