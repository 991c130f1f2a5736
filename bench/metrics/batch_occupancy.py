"""Scheduler: decoded tokens over the slots the decode steps carried
(``tokens_decoded / (decode_steps * slots)``, window deltas of the
engine's counters).  Moves ``tok_per_s``."""


def read(rec):
    steps = rec["stats"]["decode_steps"]
    if not steps:
        return None
    return rec["stats"]["tokens_decoded"] / (steps * rec["slots"])
