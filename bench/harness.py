"""One run of one cell: set up, drive the measured window, check.

The system under test is the program's ``AsyncServeEngine``, entered
through ``submit_async`` and driven by this file's pump loop.  From the
program the benchmark takes only that engine, its counters and cache
arrays, and the names of its compiled programs; the traffic, the window
arithmetic, the trace reduction, the work counts, the peaks and the
reference that decides ``correct`` are the benchmark's own.  What knows
the model's shape (the program's config, the reference's layers, the K/V
element count, the work counts, the kernel names) is the architecture
module the configuration names (``cell.arch``, ``bench/arch/<arch>.py``).

Phases (host spans ``bench.*`` mark each on the profiler's clock):

1. set-up: compile cache, weights from the seed in one jitted call, the
   engine, then a short trace of the mix from another seed that reaches
   every prefill program key the mix makes;
2. the window of ``seconds``, closed at the end of the dispatch then in
   flight: open loop (every arrival scheduled in the window, submitted
   with its scheduled time) or closed loop (a fixed number of clients,
   each sending its next request when the last closes); compiles inside
   it are counted;
3. the drain: requests still in flight finish, for their latencies only;
4. memory, then the program's state is freed and the float32 reference
   re-derives the logits of a seeded sample of finished requests.

What decides ``correct`` (``judge``): the widest and the mean gap by
which a served token's reference logit lies below the reference's best,
the bits the engine's K/V pool holds per element against the precision
the configuration states, every request due in the window finished, and
every token in the vocabulary.  Each number has its limit in
``bench/cells/<cell>.json``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import logging
import os
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import reference, registry, traffic, window

#: salt for the seed of the warm-up trace, so it never repeats the window's
WARM_SALT = 0x5EED
#: requests re-derived by the reference: the longest finished one and a
#: seeded draw of the others
CHECK_REQUESTS = 8
#: bits per element of the K/V precision a configuration states
DTYPE_BITS = {"float32": 32, "bfloat16": 16, "float16": 16}


def log(msg: str) -> None:
    print(msg, flush=True)


def next_pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def prefill_keys(length: int, cached: int, quantum: int, max_len: int):
    """The program keys (offset, padded length) of the prefill quanta
    that serve a ``length``-token prompt admitted with ``cached`` tokens
    in the prefix cache: the engine prefills in quanta of ``quantum``
    tokens from the cached length and pads each to a power of two."""
    keys, off = set(), cached
    while off < length:
        c = min(quantum, length - off)
        keys.add((off, min(next_pow2(c), max_len)))
        off += c
    return keys


class CompileCounter(logging.Handler):
    """Counts jit traces and XLA compiles (``jax.log_compiles``)."""

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.records: List[str] = []

    def emit(self, rec):
        msg = rec.getMessage()
        if "Finished tracing" in msg or "Compiling " in msg:
            self.records.append(msg.splitlines()[0][:200])


@contextlib.contextmanager
def count_compiles():
    import jax

    handler = CompileCounter()
    logger = logging.getLogger("jax")
    old_level, old_prop = logger.level, logger.propagate
    with jax.log_compiles():
        logger.addHandler(handler)
        logger.setLevel(logging.WARNING)   # log_compiles' level
        logger.propagate = False          # count, do not print
        try:
            yield handler.records
        finally:
            logger.removeHandler(handler)
            logger.setLevel(old_level)
            logger.propagate = old_prop


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Options:
    """What a test may change; a benchmark run takes the defaults."""
    interpret: bool = False               # Pallas interpreter (CPU tests)
    #: replaces the engine settings of the configuration file
    engine: Optional[dict] = None
    #: called with the engine after set-up (a test plants a fault here)
    tamper: Optional[Callable] = None
    #: put the reference computed in float8 in the program's place:
    #: ``correct`` judges the tokens it puts first at each position of the
    #: served streams (``bench/calibrate.py``; a benchmark run never does)
    control: bool = False


def build(cell: registry.Cell, seed: int, opts: Options):
    import jax
    import jax.numpy as jnp

    from repro.model import transformer as tf
    from repro.model.layers import Runtime
    from repro.serving.scheduler import AsyncServeEngine, WallClock

    class Engine(AsyncServeEngine):
        """The program's engine, observed: each prefill quantum's token
        range is logged (for the work it counts) before it dispatches.
        ``measure`` holds the log against the engine's own count of
        prefilled tokens and stops the run where they differ, so a change
        to this hook's names fails aloud, not by a lower count."""

        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self.prefill_log: List[tuple] = []

        def _prefill_quantum_dispatch(self, rid):
            st = self._mid[self._slot_of[rid]]
            c = min(self.prefill_quantum, len(st.tokens) - st.progress)
            self.prefill_log.append(
                (self.clock.now(), st.progress, st.progress + c))
            super()._prefill_quantum_dispatch(rid)

    e = dict(cell.config["engine"], **(opts.engine or {}))
    cfg = cell.arch.model_config(cell.config)
    rt = Runtime(attn_impl=e["attn_impl"], interpret=opts.interpret,
                 param_dtype=jnp.bfloat16, activation_dtype=jnp.bfloat16)
    with span("bench.weights"):
        params = jax.jit(lambda k: tf.init(cfg, k, rt)[0])(
            jax.random.PRNGKey(seed))
        jax.block_until_ready(params)
    eng = Engine(cfg, params, slots=e["slots"], max_len=e["max_len"],
                 rt=rt, dtype=jnp.bfloat16, decode_chunk=e["decode_chunk"],
                 cache_layout="paged", page_size=e["page_size"],
                 num_pages=e["num_pages"],
                 prefix_caching=e["prefix_caching"],
                 kv_dtype=e.get("kv_dtype"),
                 prefill_quantum=e["prefill_quantum"], clock=WallClock())
    return cfg, eng, e


# -- the pump loop ----------------------------------------------------------

class Driver:
    """Feeds a mix to the engine and keeps each request's record."""

    def __init__(self, eng, rid0: int = 0):
        self.eng = eng
        self.reqs: List = []
        self.records: List[window.Record] = []
        self.next_rid = rid0
        self.max_turn_s = 0.0             # longest pump turn (host stall)

    def submit(self, item: traffic.Item, arrival: float):
        from repro.serving.scheduler import AsyncRequest

        req = AsyncRequest(rid=self.next_rid, prompt=item.prompt,
                           max_new_tokens=item.output_len, arrival=arrival)
        self.next_rid += 1
        self.eng.submit_async(req)
        self.reqs.append(req)
        self.records.append(window.Record(arrival=arrival,
                                          token_times=req.token_times,
                                          expected=item.output_len))
        return req

    def turn(self, until: float) -> None:
        """One scheduler turn, or a wait for the next arrival (at most
        until ``until``)."""
        clock = self.eng.clock
        t = clock.now()
        with span("bench.pump"):
            did = self.eng.pump()
        self.max_turn_s = max(self.max_turn_s, clock.now() - t)
        if not did:
            nxt = self.eng.sched.next_arrival(clock.now())
            with span("bench.wait_arrival"):
                clock.wait_until(min(nxt, until) if nxt is not None
                                 else until)

    def pending(self) -> bool:
        return any(not r.done for r in self.reqs)

    def open_loop(self, items, t0: float, t1: float) -> None:
        with span("bench.submit"):
            for it in items:
                self.submit(it, t0 + it.arrival_s)
        while self.eng.clock.now() < t1:
            self.turn(t1)

    def closed_loop(self, supply, clients: int, t0: float, t1: float):
        with span("bench.submit"):
            live = [self.submit(next(supply), t0) for _ in range(clients)]
        clock = self.eng.clock
        while clock.now() < t1:
            self.turn(t1)
            now = clock.now()
            for i, r in enumerate(live):
                if r.done and now < t1:
                    with span("bench.submit"):
                        live[i] = self.submit(next(supply), now)

    def drain(self, limit_s: float) -> None:
        end = self.eng.clock.now() + limit_s
        with span("bench.drain"):
            while self.pending() and self.eng.clock.now() < end:
                self.turn(end)


def warm(eng, mix: traffic.Mix, e: dict, seed: int) -> int:
    """Serve a short trace of the mix, from another seed, that reaches
    every prefill program key the mix can make and both decode programs
    (one step for a fresh slot, ``decode_chunk`` steps after): the shapes
    this cell's traffic uses and no others.  Returns the number of
    warm-up requests served."""
    items = mix.warm_requests(
        lambda n, c: prefill_keys(n, c, e["prefill_quantum"], e["max_len"]),
        seed ^ WARM_SALT)
    d = Driver(eng, rid0=-(1 << 40))
    with span("bench.warm_trace"):
        for it in items:
            d.submit(it, eng.clock.now())
            d.drain(600.0)
    eng.clear_prefix_cache()
    return len(items)


# -- one run -----------------------------------------------------------------

def process_start() -> float:
    """Wall time at which this process started (Linux), else now."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return time.time() - (up - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return time.time()


def sample_for_check(reqs, seed: int, k: int = CHECK_REQUESTS):
    """The longest finished request and a seeded draw of the others."""
    done = [r for r in reqs if r.done and r.generated]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.generated), len(r.prompt)))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False) \
        if rest else []
    return [longest] + [rest[i] for i in sorted(pick)]


def check(arch, config: dict, seed: int, sample, max_len: int,
          precisions=("f32",)) -> Dict[str, np.ndarray]:
    """Reference gaps of every served token of ``sample``: for each
    precision, how far the reference's logit of the token that precision
    (f32: the program) put first lies below the reference's best."""
    seqs, poss, served = [], [], []
    for r in sample:
        s, p, g = reference.teacher_forced(r.prompt, r.generated)
        seqs.append(s)
        poss.append(p)
        served.append(g)
    ref = reference.logits_at(arch, config, seed, seqs, poss, max_len,
                              "f32")
    out = {"program": np.concatenate(
        [reference.served_gaps(l, g) for l, g in zip(ref, served)])}
    for prec in precisions:
        if prec == "f32":
            continue
        ctl = reference.logits_at(arch, config, seed, seqs, poss, max_len,
                                  prec)
        out[prec] = np.concatenate(
            [reference.served_gaps(l, c.argmax(-1)) for l, c in
             zip(ref, ctl)])
    return out


def kv_bits(eng, cell: registry.Cell) -> float:
    """Bits the engine's cache arrays hold per element of the K/V pool the
    configuration describes: every byte of ``engine.caches`` over the
    architecture's count of the pool's elements."""
    import jax

    return 8.0 * sum(x.nbytes for x in jax.tree.leaves(eng.caches)) \
        / cell.arch.kv_elements(cell.config)


def judge(cell: registry.Cell, gaps: np.ndarray, m: dict, bits: float,
          bad_tokens: int) -> dict:
    """Each number that decides ``correct``, beside its limit."""
    stated = DTYPE_BITS[cell.config["torch_dtype"]]
    return {
        "max_logit_gap": {"value": float(gaps.max()),
                          "limit": cell.checks.get("max_logit_gap", 0.0)},
        "mean_logit_gap": {"value": float(gaps.mean()),
                           "limit": cell.checks.get("mean_logit_gap", 0.0)},
        "kv_bits_short": {"value": stated - bits, "limit": 0},
        "failed_requests": {"value": m["failed"], "limit": 0},
        "out_of_vocab_tokens": {"value": bad_tokens, "limit": 0},
    }


def decode_contexts(reqs, lo: float, hi: float) -> List[int]:
    """Context length (keys read, itself included) of every token decoded
    in ``[lo, hi]``: the i-th generated token of a prompt of length L is
    fed to the decode step at position L+i and reads L+i+1 keys."""
    out = []
    for r in reqs:
        n = len(r.prompt)
        for i, t in enumerate(r.token_times):
            if lo <= t <= hi:
                out.append(n + i + 1)
    return out


@dataclasses.dataclass
class Session:
    """A built and warmed engine for one cell."""
    cell: registry.Cell
    cfg: object
    eng: object
    e: dict
    dev: object
    peak: Optional[dict]
    t_proc: float
    next_rid: int = 0


def setup(root: str, workload: str, seed: int,
          opts: Optional[Options] = None) -> Session:
    import jax

    opts = opts or Options()
    t_proc = process_start()
    cell = registry.load_cell(root, workload)
    dev = jax.devices()[0]
    peak = registry.peaks(dev.device_kind) if not opts.interpret else None

    from repro.serving.engine import enable_compilation_cache

    log(f"compile cache: {enable_compilation_cache()}")
    t = time.perf_counter()
    cfg, eng, e = build(cell, seed, opts)
    t_build = time.perf_counter() - t
    t = time.perf_counter()
    n_warm = warm(eng, traffic.Mix(cell.mix, cfg.vocab, e["max_len"], seed),
                  e, seed)
    log(f"set-up: weights and engine {t_build:.2f}s, warm-up trace of "
        f"{n_warm} requests {time.perf_counter() - t:.2f}s")
    return Session(cell, cfg, eng, e, dev, peak, t_proc)


def reseed(s: Session, seed: int) -> None:
    """New weights from ``seed`` in the same engine and programs (the
    calibration's many seeds in one process)."""
    import jax

    from repro.model import transformer as tf

    s.eng.params = None                   # two sets of weights may not fit
    gc.collect()
    s.eng.params = jax.jit(lambda k: tf.init(s.cfg, k, s.eng.rt)[0])(
        jax.random.PRNGKey(seed))
    jax.block_until_ready(s.eng.params)
    s.eng.clear_prefix_cache()


def measure(s: Session, seed: int, seconds: float, trace: bool,
            opts: Optional[Options] = None, free: bool = True) -> dict:
    """The window, the drain, memory, and the check.  With ``free`` the
    program's state is dropped before the reference runs."""
    import jax

    opts = opts or Options()
    eng, e, cell = s.eng, s.e, s.cell
    mix = traffic.Mix(cell.mix, s.cfg.vocab, e["max_len"], seed)
    if opts.tamper is not None:
        opts.tamper(eng)
    drv = Driver(eng, rid0=s.next_rid)
    clock = eng.clock
    trace_dir = None
    if trace:
        trace_dir = os.path.join(cell.bench_dir, "out", "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir, profiler_options=_profile_opts())
    stats0 = dict(eng.stats)
    plog0 = len(eng.prefill_log)
    t0 = clock.now()
    setup_s = time.time() - s.t_proc
    t1 = t0 + seconds
    with count_compiles() as compiles, span("bench.window"):
        if mix.open_loop:
            drv.open_loop(mix.open_requests(seconds), t0, t1)
        else:
            drv.closed_loop(mix.closed_requests(), mix.clients(e["slots"]),
                            t0, t1)
        t_end = clock.now()                # the dispatch in flight ended
    stats1 = dict(eng.stats)
    if trace:
        jax.profiler.stop_trace()
    backlog = sum(1 for r in drv.reqs if not r.done)
    t_drain = clock.now()
    drv.drain(240.0)
    drain_s = clock.now() - t_drain
    m = window.window_metrics(drv.records, t0, t_end)
    log(f"window {t_end - t0:.3f}s: {m['attempted']} requests due, "
        f"{m['tokens_in_window']} tokens delivered in it; ttft p50 "
        f"{m['ttft_p50_ms']} ms over {m['ttft_samples']}, tpot p50 "
        f"{m['tpot_p50_ms']} ms over {m['tpot_samples']}; set-up "
        f"{setup_s:.3f}s")
    log(f"at the window's close {backlog} requests were unfinished; the "
        f"drain took {drain_s:.2f}s")
    log(f"compiles inside the window: {len(compiles)}"
        + "".join(f"\n  {c}" for c in compiles[:5]))
    log(f"longest pump turn {drv.max_turn_s * 1e3:.1f} ms (arrivals are "
        f"scheduled, so the generator itself never runs late)")
    delta = {k: stats1[k] - stats0[k] for k in stats1}
    log("engine counters in the window: " + ", ".join(
        f"{k} {v}" for k, v in delta.items() if v))
    prefills = [(a, b) for t, a, b in eng.prefill_log[plog0:]
                if t0 <= t <= t_end]
    if sum(b - a for a, b in prefills) != delta["tokens_prefilled"]:
        raise RuntimeError(
            f"the harness saw {sum(b - a for a, b in prefills)} tokens "
            f"prefilled in the window where the engine counted "
            f"{delta['tokens_prefilled']}: its hook on the engine's prefill "
            f"quantum (bench/harness.py, build) no longer sees every one")
    bits = kv_bits(eng, cell)

    # memory: what the runtime saw, and what the decode program asks for
    mem = s.dev.memory_stats() or {}
    decode_bytes = decode_program_bytes(eng)
    peak_bytes = max(int(mem.get("peak_bytes_in_use", 0)), decode_bytes)
    log(f"peak_bytes_in_use {mem.get('peak_bytes_in_use')}; decode program "
        f"arguments + temporaries {decode_bytes}")

    record = None
    if trace:
        from bench import trace as trace_mod

        t = time.perf_counter()
        red = trace_mod.reduce(trace_mod.load(_xplane(trace_dir)),
                               cell.arch.KERNELS)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace reduced in {time.perf_counter() - t:.1f}s: programs "
            f"{red.programs} over {red.program_runs} runs, kernels "
            f"{red.kernels}")
        record = {
            "trace": red, "stats": delta, "slots": e["slots"],
            "shapes": cell.arch.Shapes.from_config(cell.config),
            "peak": s.peak,
            "decode_ctx": decode_contexts(drv.reqs, t0, t_end),
            "prefills": prefills,
            "decode_hbm_bytes": decode_bytes,
        }

    reqs = drv.reqs
    sample = sample_for_check(reqs, seed)
    s.next_rid = drv.next_rid
    del drv
    if free:
        s.eng = eng = None
        gc.collect()
    t = time.perf_counter()
    precisions = ("f32", "fp8") if opts.control else ("f32",)
    read = check(cell.arch, cell.config, seed, sample, e["max_len"],
                 precisions) \
        if sample else {"program": np.array([np.inf])}
    # the control, where one is asked for, stands in the program's place
    gaps = read.get("fp8", read["program"]) if opts.control \
        else read["program"]
    bad_tokens = sum(int(((np.asarray(r.generated) < 0)
                          | (np.asarray(r.generated) >= s.cfg.vocab)).sum())
                     for r in reqs if r.generated)
    checks = judge(cell, gaps, m, bits, bad_tokens)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    log(f"reference over {len(sample)} requests, {gaps.size} served tokens, "
        f"in {time.perf_counter() - t:.1f}s; gap median "
        f"{float(np.median(gaps)):.4f}")

    if trace:
        metrics = registry.read_metrics(cell, record)
    else:
        metrics = {
            "tok_per_s": {"value": m["tok_per_s"], "unit": "tokens/s"},
            "ttft_p95_ms": {"value": m["ttft_p95_ms"], "unit": "ms"},
            "tpot_p95_ms": {"value": m["tpot_p95_ms"], "unit": "ms"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        metrics = {k: v for k, v in metrics.items()
                   if any(x["name"] == k for x in cell.end_to_end)}
    device = {"platform": s.dev.platform, "kind": s.dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}
    out = {"correct": bool(correct), "attempted": m["attempted"],
           "failed": m["failed"], "metrics": metrics, "device": device}
    if trace:
        red = record["trace"]
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        out["breakdown"] = {"device_ops": [list(x) for x in red.device_ops],
                            "idle_gaps": [list(x) for x in red.idle_gaps]}
    out["load"] = {"backlog_at_close": backlog, "drain_s": drain_s,
                   "compiles_in_window": len(compiles),
                   **{k: m[k] for k in ("tok_per_s", "ttft_p50_ms",
                                        "ttft_p95_ms", "tpot_p95_ms")}}
    if opts.control:
        out["readings"] = {k: {"max": float(v.max()),
                               "mean": float(v.mean()),
                               "flipped": float((v > 0).mean())}
                           for k, v in read.items()}
    out["checks"] = checks
    return out


def run(root: str, workload: str, seed: int, seconds: float, trace: bool,
        opts: Optional[Options] = None) -> dict:
    """One run of a cell; returns the contract's result object."""
    s = setup(root, workload, seed, opts)
    return measure(s, seed, seconds, trace, opts)


def decode_program_bytes(eng) -> int:
    """Arguments + temporaries of the compiled fused decode program, as
    the compiler reports them (``memory_analysis``)."""
    fn = eng._get_loop(eng.decode_chunk)
    if not hasattr(fn, "lower"):          # replaced by a test's fault
        return 0
    args = (eng.params, eng.caches, eng._kv_len, eng._last_logits,
            eng._remaining, eng.key, eng.kv.tables())
    ma = fn.lower(*args).compile().memory_analysis()
    if ma is None:
        return 0
    return int(ma.argument_size_in_bytes + ma.temp_size_in_bytes)


def _profile_opts():
    import jax

    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0             # benchmark spans only
    o.host_tracer_level = 2
    return o


def _xplane(d: str) -> str:
    for dirpath, _, files in os.walk(d):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(dirpath, f)
    raise FileNotFoundError(f"no .xplane.pb under {d}")


def print_checks(checks: dict) -> None:
    for k, c in checks.items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
