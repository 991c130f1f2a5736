"""The one traffic generator: reads a mix's parameters, makes requests.

A mix is a data file, ``bench/traffic/<mix>.json``; a cell may override
its numbers (such as the arrival rate) in ``bench/cells/<cell>.json``.
Keys of a mix:

``loop``            "open" (Poisson arrivals at ``rate_per_s``) or
                    "closed" (``clients_per_slot`` clients per engine
                    slot, each sending its next request when its last
                    one closes).
``prompt``          length of the prompt, or of the user turn after a
                    shared prefix: ``{"min", "max", "median", "sigma"}``
                    of a log-normal, clipped to ``[min, max]``.
``output``          tokens to generate, the same form.
``shared_prefix``   optional ``{"count", "tokens", "zipf_s"}``: every
                    prompt starts with one of ``count`` system prompts of
                    ``tokens`` tokens, chosen with Zipf popularity.
``block``           requests per quantile set (below; default 16).

Steadiness: the lengths, prefix choices and arrival gaps, and their order,
do not depend on the run's seed.  Requests come in blocks of ``block``;
each block holds the same ``block`` quantiles of each distribution, in an
order drawn from ``ORDER_SEED``, and the run's seed draws the token ids
(uniform over the vocabulary) and the system prompts.  So every seed
offers the same sequence of work, and a difference between runs is the
system's, not the draw's: in a closed loop a tail such as the 95th
percentile of the wait for a slot rests on a few dozen requests, and
which lengths happen to share the slots would move it far more than the
system's own noise does.  The arrival gaps of an open loop are one set over
the whole window; its requests are whole blocks where ``block`` divides
``rate_per_s`` x the window.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Iterator, List, Optional

import numpy as np

#: seed of the order in which each block's quantiles are served: the same
#: for every run, so every run offers the same sequence of work
ORDER_SEED = 0


@dataclasses.dataclass
class Item:
    """One request as the mix makes it: arrival offset from the window's
    start (open loop; None for closed loop), prompt tokens, output
    length, and which system prompt it starts with (-1: none)."""
    prompt: np.ndarray
    output_len: int
    arrival_s: Optional[float] = None
    prefix_id: int = -1


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_set(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of the clipped log-normal."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf(q) for q in _quantiles(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def zipf_set(count: int, s: float, n: int) -> np.ndarray:
    """``n`` draws of ``0..count-1`` with probability ∝ 1/(k+1)^s, as the
    quantiles of that distribution (so each id appears in proportion)."""
    p = 1.0 / np.arange(1, count + 1) ** s
    cdf = np.cumsum(p / p.sum())
    return np.minimum(np.searchsorted(cdf, _quantiles(n)), count - 1)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps at the quantiles of Exp(rate), scaled so
    their mean is exactly ``1/rate``: ``n`` requests span ``n/rate``."""
    g = -np.log1p(-_quantiles(n))
    return g / g.mean() / rate


class Mix:
    """A traffic mix bound to a vocabulary, a context limit and a seed."""

    def __init__(self, params: dict, vocab: int, max_len: int, seed: int):
        self.p = params
        self.vocab = vocab
        self.max_len = max_len
        self.seed = seed
        self.rng = np.random.default_rng(seed)
        self.order = np.random.default_rng(ORDER_SEED)
        sp = params.get("shared_prefix")
        self.prefixes: List[np.ndarray] = []
        if sp:
            self.prefixes = [self._tokens(sp["tokens"])
                             for _ in range(sp["count"])]

    @property
    def open_loop(self) -> bool:
        return self.p["loop"] == "open"

    def _tokens(self, n: int) -> np.ndarray:
        return self.rng.integers(0, self.vocab, size=(n,)).astype(np.int32)

    def _prefix_len(self) -> int:
        sp = self.p.get("shared_prefix")
        return sp["tokens"] if sp else 0

    def _make(self, plen: int, olen: int, pid: int,
              arrival: Optional[float]) -> Item:
        head = self.prefixes[pid] if pid >= 0 else np.zeros(0, np.int32)
        prompt = np.concatenate([head, self._tokens(plen)])
        # prompt + output must leave the last cache position free
        olen = int(min(olen, self.max_len - 1 - len(prompt)))
        if olen < 1:
            raise ValueError(
                f"mix makes a {len(prompt)}-token prompt, which leaves no "
                f"room to decode under max_len={self.max_len}")
        return Item(prompt=prompt, output_len=olen, arrival_s=arrival,
                    prefix_id=pid)

    @property
    def block(self) -> int:
        return int(self.p.get("block", 16))

    def _sets(self, n: int):
        """Prompt lengths, output lengths and prefix ids of ``n`` requests:
        whole blocks of the quantile sets, each block shuffled in the
        one fixed order, cut to ``n``."""
        b = self.block
        sp = self.p.get("shared_prefix")
        blocks = []
        for _ in range(-(-n // b)):
            blocks.append((
                self.order.permutation(lognormal_set(self.p["prompt"], b)),
                self.order.permutation(lognormal_set(self.p["output"], b)),
                self.order.permutation(zipf_set(sp["count"],
                                              sp.get("zipf_s", 1.0), b))
                if sp else np.full(b, -1)))
        return tuple(np.concatenate(x)[:n] for x in zip(*blocks))

    def open_requests(self, seconds: float) -> List[Item]:
        """Every request that arrives in a window of ``seconds``:
        ``floor(rate·seconds)`` of them, the last before the window
        closes."""
        rate = float(self.p["rate_per_s"])
        n = max(1, int(math.floor(rate * seconds)))
        plens, olens, pids = self._sets(n)
        gaps = self.order.permutation(exponential_gaps(rate, n))
        # gap i precedes request i; the n gaps sum to n/rate <= seconds
        arrivals = np.cumsum(gaps) - gaps[0] * 0.5
        return [self._make(int(a), int(b), int(c), float(t))
                for a, b, c, t in zip(plens, olens, pids, arrivals)]

    def closed_requests(self) -> Iterator[Item]:
        """The closed loop's supply, in submission order, one block at a
        time, without end."""
        while True:
            for a, b, c in zip(*self._sets(self.block)):
                yield self._make(int(a), int(b), int(c), None)

    def clients(self, slots: int) -> int:
        return int(self.p["clients_per_slot"] * slots)

    # -- warm-up -----------------------------------------------------------

    def prompt_lengths(self) -> List[int]:
        """Every prompt length this mix makes: its block's quantiles."""
        base = self._prefix_len()
        return sorted({base + int(x) for x in
                       lognormal_set(self.p["prompt"], self.block)})

    def warm_requests(self, keys, seed: int) -> List[Item]:
        """A short trace of this mix, drawn from another seed, whose
        prompts reach every program key that ``keys(length, cached)``
        names for a prompt of ``length`` tokens admitted with ``cached``
        of them already in the prefix cache.  With a shared prefix the
        first request is cold and registers system prompt 0, so that
        later ones find it cached; the rest are a greedy cover of the
        keys not reached yet."""
        warm = Mix(self.p, self.vocab, self.max_len, seed)
        base = self._prefix_len()
        want = {}
        for length in self.prompt_lengths():
            for cached in ((0, base) if base else (0,)):
                want[(length, cached)] = set(keys(length, cached))
        out: List[Item] = []
        todo = set().union(*want.values())
        if base:
            out.append(warm._make(max(self.prompt_lengths()) - base, 2, 0,
                                  None))
            todo -= want[(len(out[0].prompt), 0)]
        while todo:
            (length, cached), ks = max(want.items(),
                                       key=lambda kv: len(kv[1] & todo))
            if cached:
                out.append(warm._make(length - base, 2, 0, None))
            else:
                # cold: a head of fresh tokens that no other prompt shares
                item = warm._make(length - base, 2, -1, None)
                item.prompt = np.concatenate([warm._tokens(base),
                                              item.prompt])
                out.append(item)
            todo -= ks
        return out
