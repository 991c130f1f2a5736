"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time,
time per program and per kernel, and the breakdown of a traced run.

What a TPU trace holds (as recorded on a v5e): each chip is a plane
``/device:TPU:<n>``.  Its line ``XLA Modules`` has one event per program
execution, named ``<jit name>(<fingerprint>)``; its line ``XLA Ops`` has
one event per HLO operation run, named by the HLO text
(``%fusion.12 = bf16[...] fusion(...)``), nested where an operation holds
others (a ``while`` holds its body).  A Pallas kernel is a custom call
whose text holds ``custom_call_target="tpu_custom_call"``, and the
operation is named after the kernel (``pallas_call(name=...)``):
``%fusemax_decode_paged.7 = ...``.  The benchmark's host spans
(``jax.profiler.TraceAnnotation``, named ``bench.*``) and the serving
engine's (``engine.*``) are on the ``/host:CPU`` plane, on the same clock.

Programs are picked out by the name table below; kernels by the table of
the configuration's architecture module (``KERNELS`` in
``bench/arch/<arch>.py``): kernel -> (program class, Pallas kernel name),
an operation counting for a kernel where it runs inside a program of
that class and its name, less its ``.<n>`` suffixes, is the kernel's.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re
from typing import Dict, List, Optional, Tuple

#: program class -> prefix of the ``XLA Modules`` event name
PROGRAMS: Dict[str, str] = {
    "decode": "jit_loop(",
    "prefill": "jit_prefill_into_slots(",
}
#: prefixes of the host spans: the benchmark's own and the engine's phases
HOST_SPAN_PREFIX = ("bench.", "engine.")
WINDOW_SPAN = "bench.window"
TOP = 10


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                          # averaged over the chips used
    chips: int
    programs: Dict[str, float]             # class -> device seconds
    program_runs: Dict[str, int]           # class -> executions
    kernels: Dict[str, float]              # kernel -> device seconds
    device_ops: List[Tuple[str, float]]    # top ops by self time
    idle_gaps: List[Tuple[str, float]]     # longest gaps, by host span

    @property
    def idle_frac(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _union_length(intervals: List[Tuple[float, float]]) -> Tuple[
        float, List[Tuple[float, float]]]:
    """Total length of the union, and the union's pieces in order."""
    pieces: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if pieces and s <= pieces[-1][1]:
            if e > pieces[-1][1]:
                pieces[-1] = (pieces[-1][0], e)
        else:
            pieces.append((s, e))
    return sum(e - s for s, e in pieces), pieces


def _short(name: str) -> str:
    head = name.split(" = ", 1)[0].lstrip("%")
    if 'custom_call_target="tpu_custom_call"' in name:
        head += " (pallas)"
    return head


def _op_base(name: str) -> str:
    """The operation's name without its ``.<n>`` suffixes:
    ``%fusemax_decode_paged.7 = ...`` -> ``fusemax_decode_paged``."""
    return re.sub(r"(\.\d+)+$", "", name.split(" = ", 1)[0].lstrip("%"))


def _classify(mod: Optional[str], name: str,
              kernels: Dict[str, Tuple[str, str]]) -> Tuple[List[str], str]:
    """The kernels of the table that the op ``name`` run inside the
    program ``mod`` counts for, and its ``<program>/<op>`` label."""
    cls = _program_class(mod) if mod else None
    base = _op_base(name)
    knames = [k for k, (kcls, pallas_name) in kernels.items()
              if cls == kcls and base == pallas_name]
    prog = mod.split("(", 1)[0] if mod else "?"
    return knames, f"{prog}/{_short(name)}"


def _program_class(module_name: str) -> Optional[str]:
    for cls, prefix in PROGRAMS.items():
        if module_name.startswith(prefix):
            return cls
    return None


def _clip(s: float, e: float, lo: float, hi: float) -> float:
    return max(0.0, min(e, hi) - max(s, lo))


def _host_spans(pd) -> List[Tuple[float, float, str]]:
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(HOST_SPAN_PREFIX):
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name))
    return out


def reduce(pd, kernels: Dict[str, Tuple[str, str]]) -> Reduced:
    """Reduce a loaded ``jax.profiler.ProfileData`` over its
    ``bench.window`` span (or, without one, over the device events),
    counting the ``kernels`` of the architecture's table."""
    host = _host_spans(pd)
    win = [(s, e) for s, e, n in host if n == WINDOW_SPAN]
    devices = [p for p in pd.planes if p.name.startswith("/device:TPU:")]

    per_chip = []
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        mods = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                for ev in (lines["XLA Modules"].events
                           if "XLA Modules" in lines else ())]
        ops = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
               for ev in (lines["XLA Ops"].events
                          if "XLA Ops" in lines else ())]
        if mods or ops:
            per_chip.append((sorted(mods), ops))
    if not per_chip:
        raise ValueError("trace holds no device events")
    if win:
        lo, hi = win[0]
    else:
        lo = min(s for m, o in per_chip for s, _, _ in (m + o))
        hi = max(e for m, o in per_chip for _, e, _ in (m + o))

    busy_total = 0.0
    programs: Dict[str, float] = collections.Counter()
    runs: Dict[str, int] = collections.Counter()
    kernel_s: Dict[str, float] = collections.Counter()
    self_time: Dict[str, float] = collections.Counter()
    gaps_all: List[Tuple[float, float]] = []
    seen: Dict[Tuple[Optional[str], str], Tuple[List[str], str]] = {}
    for mods, ops in per_chip:
        starts = [s for s, _, _ in mods]
        for s, e, name in mods:
            cls = _program_class(name)
            t = _clip(s, e, lo, hi)
            if cls is not None and t > 0:
                programs[cls] += t
                runs[cls] += 1

        def module_of(s: float, e: float) -> Optional[str]:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and mods[i][1] >= e:
                return mods[i][2]
            return None

        # self time: an op's duration less the ops nested inside it
        ordered = sorted(ops, key=lambda o: (o[0], -o[1]))
        selfs = [e - s for s, e, _ in ordered]
        stack: List[int] = []
        for i, (s, e, name) in enumerate(ordered):
            while stack and ordered[stack[-1]][1] <= s:
                stack.pop()
            if stack:
                selfs[stack[-1]] -= e - s
            stack.append(i)
        intervals = []
        for (s, e, name), st in zip(ordered, selfs):
            t = _clip(s, e, lo, hi)
            if t <= 0:
                continue
            intervals.append((max(s, lo), min(e, hi)))
            mod = module_of(s, e)
            # a trace repeats each (program, op) name over many events:
            # each is parsed once
            hit = seen.get((mod, name))
            if hit is None:
                hit = seen[mod, name] = _classify(mod, name, kernels)
            knames, op = hit
            for kname in knames:
                kernel_s[kname] += t
            self_time[op] += max(0.0, st) * (t / (e - s) if e > s else 1.0)
        busy, pieces = _union_length(intervals or
                                     [(max(s, lo), min(e, hi))
                                      for s, e, _ in mods if e > lo
                                      and s < hi])
        busy_total += busy
        prev = lo
        for s, e in pieces:
            if s > prev:
                gaps_all.append((prev, s))
            prev = max(prev, e)
        if hi > prev:
            gaps_all.append((prev, hi))

    def label(t: float) -> str:
        inner = None
        for s, e, n in host:
            if s <= t < e and n != WINDOW_SPAN and (
                    inner is None or s >= inner[0]):
                inner = (s, e, n)
        return inner[2] if inner else "no benchmark span"

    gaps = sorted(gaps_all, key=lambda g: g[1] - g[0], reverse=True)[:TOP]
    n = len(per_chip)
    return Reduced(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_total / n * 1e-9,
        chips=n,
        programs={k: v / n * 1e-9 for k, v in programs.items()},
        program_runs={k: v // n for k, v in runs.items()},
        kernels={k: v / n * 1e-9 for k, v in kernel_s.items()},
        device_ops=[(k, v / n * 1e-9)
                    for k, v in sorted(self_time.items(),
                                       key=lambda kv: kv[1],
                                       reverse=True)[:TOP]],
        idle_gaps=[(label((s + e) / 2), (e - s) * 1e-9) for s, e in gaps],
    )


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)
