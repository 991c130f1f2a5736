"""End-to-end metrics of one measured window, from the request log.

Each request record carries its scheduled arrival and the host time at
which each of its output tokens reached the client.  The window is the
half-open interval ``[t0, t1)``.  The harness closes it at the end of the
dispatch in flight once ``seconds`` have passed: tokens reach the host a
whole fused decode dispatch at a time (16 steps of every slot, ~0.6 s),
so a close in mid-dispatch would count or drop a dispatch's tokens by the
phase of the close alone.

``tok_per_s``   output tokens delivered inside the window, over its length:
                a rate over all the work and all the time of the window,
                so a stall anywhere in it counts.  Tokens delivered after
                the window (the drain of requests still in flight) do not.
``ttft_ms``     first-token time minus *scheduled* arrival, so queueing and
                stalls count, over every request due in the window.
``tpot_ms``     (last-token time - first-token time) / (tokens - 1), per
                request due in the window with at least two tokens.  It
                replaces pooled inter-token gaps: the engine delivers 16
                tokens per dispatch with one timestamp, so 15 of every 16
                pooled gaps are 0 and their tail only finds the chunk
                boundaries.

The arithmetic of TTFT from arrival follows the program's own
``serving.scheduler.latency_metrics``; its pooled ITL and its tok/s over
a span that includes the drain are what is replaced here.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import numpy as np


@dataclasses.dataclass
class Record:
    arrival: float
    token_times: List[float]
    expected: int                    # output tokens asked for

    @property
    def done(self) -> bool:
        return len(self.token_times) >= self.expected


def percentile(xs: Sequence[float], q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if len(xs) \
        else None


def window_metrics(records: Sequence[Record], t0: float, t1: float) -> dict:
    """The window's end-to-end numbers and the counts behind them."""
    due = [r for r in records if t0 <= r.arrival < t1]
    delivered = sum(1 for r in records for t in r.token_times
                    if t0 <= t < t1)
    ttft = [(r.token_times[0] - r.arrival) * 1e3 for r in due
            if r.token_times]
    tpot = [(r.token_times[-1] - r.token_times[0]) * 1e3
            / (len(r.token_times) - 1)
            for r in due if len(r.token_times) >= 2]
    return {
        "attempted": len(due),
        "failed": sum(1 for r in due if not r.done),
        "tokens_in_window": delivered,
        "tok_per_s": delivered / (t1 - t0),
        "ttft_p95_ms": percentile(ttft, 95),
        "ttft_p50_ms": percentile(ttft, 50),
        "tpot_p95_ms": percentile(tpot, 95),
        "tpot_p50_ms": percentile(tpot, 50),
        "ttft_samples": len(ttft),
        "tpot_samples": len(tpot),
    }
