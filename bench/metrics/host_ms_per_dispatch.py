"""Scheduler: the serving engine's host time per device dispatch that is
not spent waiting on the chip, in ms: ``(host_ns_pump -
host_ns_decode_wait)`` over the prefill and decode dispatches, window
deltas of the engine's counters.  Nothing to read where the program has
no such counters.  Moves ``tok_per_s``."""


def read(rec):
    s = rec["stats"]
    n = s["decode_dispatches"] + s["prefill_dispatches"]
    if "host_ns_pump" not in s or not n:
        return None
    return (s["host_ns_pump"] - s["host_ns_decode_wait"]) / n / 1e6
