"""What the serving engine and its kernels name in a profiler trace.

The async engine marks each host phase of a scheduler turn with a span
``engine.<phase>`` and adds its nanoseconds to ``stats["host_ns_<phase>"]``
(``serving.engine.HOST_PHASES``); each request records when it was first
admitted, on the engine's clock.  The Pallas kernels carry their own
names into the program, so a trace can tell them apart.
"""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.model import transformer as tf
from repro.model.layers import Runtime
from repro.serving import (
    AsyncRequest, AsyncServeEngine, Request, ServeEngine, VirtualClock,
)
from repro.serving.engine import HOST_PHASES

RT = Runtime(activation_dtype=jnp.float32, param_dtype=jnp.float32)
COUNTERS = ["host_ns_" + p.replace(".", "_") for p in HOST_PHASES]
CHILDREN = [c for c in COUNTERS if c != "host_ns_pump"]


@pytest.fixture(scope="module")
def smoke():
    cfg = get_config("stablelm-1.6b-smoke")
    params, _ = tf.init(cfg, jax.random.PRNGKey(0), RT)
    return cfg, params


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in lens]


def _engine(cfg, params, clock=None, **kw):
    kw = dict(dict(slots=2, max_len=64, page_size=8, prefill_quantum=8),
              **kw)
    return AsyncServeEngine(cfg, params, rt=RT, temperature=0.0,
                            cache_layout="paged", prefix_caching=True,
                            clock=clock or VirtualClock(), **kw)


def _requests(cfg, lens, arrivals, budget=6, seed=0):
    return [AsyncRequest(rid=i, prompt=p, max_new_tokens=budget,
                         arrival=a)
            for i, (p, a) in enumerate(zip(_prompts(cfg, lens, seed),
                                           arrivals))]


def _pump_with_ticks(eng, reqs, dt=1.0):
    """Pump with the clock one ``dt`` further each turn; returns, per
    request, the clock value of every turn that took it from waiting to
    admitted (a preempted request is admitted again)."""
    for r in reqs:
        eng.submit_async(r)
    admissions = {r.rid: [] for r in reqs}
    for _ in range(10_000):
        if all(r.done for r in reqs):
            break
        eng.clock.advance(dt)
        t = eng.clock.now()
        waiting = {rid for rid, e in eng.sched.entries.items()
                   if e.state == "waiting"}
        eng.pump()
        for rid in waiting:
            if eng.sched.entries[rid].state != "waiting":
                admissions[rid].append(t)
    assert all(r.done for r in reqs)
    return admissions


def test_every_phase_counter_is_declared_and_grows(smoke):
    cfg, params = smoke
    eng = _engine(cfg, params)
    assert all(eng.stats[c] == 0 for c in COUNTERS)
    eng.serve_trace(_requests(cfg, [20, 12, 30], [0.0] * 3))
    for c in COUNTERS:
        assert eng.stats[c] > 0, c


def test_pump_holds_the_sum_of_its_children(smoke):
    cfg, params = smoke
    eng = _engine(cfg, params)
    eng.serve_trace(_requests(cfg, [20, 12, 30, 9], [0.0] * 4))
    children = sum(eng.stats[c] for c in CHILDREN)
    assert 0 < children <= eng.stats["host_ns_pump"]
    # one decode dispatch spends its time in the four decode phases
    assert eng.stats["host_ns_decode_wait"] > 0
    assert eng.stats["decode_dispatches"] > 0 and \
        eng.stats["prefill_dispatches"] > 0


def test_sync_engine_times_its_decode_phases_and_warmup_zeroes(smoke):
    cfg, params = smoke
    eng = ServeEngine(cfg, params, rt=RT, slots=2, max_len=64,
                      cache_layout="paged", page_size=8)
    eng.warmup(8)
    assert all(eng.stats[c] == 0 for c in COUNTERS)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=5)
            for i, p in enumerate(_prompts(cfg, [6, 10]))]
    for r in reqs:
        eng.submit(r)
    eng.run()
    for c in ("host_ns_decode_prepare", "host_ns_decode_launch",
              "host_ns_decode_wait", "host_ns_decode_harvest"):
        assert eng.stats[c] > 0, c
    # no scheduler turns on the synchronous engine
    assert eng.stats["host_ns_pump"] == 0


def test_admitted_at_is_the_engine_clock_at_first_admission(smoke):
    cfg, params = smoke
    eng = _engine(cfg, params, slots=1)
    reqs = _requests(cfg, [12, 9, 15], [0.0, 0.5, 2.0])
    admissions = _pump_with_ticks(eng, reqs)
    for r in reqs:
        assert r.admitted_at == admissions[r.rid][0]
        assert r.arrival <= r.admitted_at <= r.token_times[0]
    # one slot: each request waits for the one before it to finish
    waits = [r.admitted_at - r.arrival for r in reqs]
    assert waits[0] == 1.0 and waits[1] > 1.0 and waits[2] > 0.0
    assert reqs[1].admitted_at >= reqs[0].token_times[-1]
    # the async engine stamps no second, wall-clock submit time
    assert all(r.ttft is None and not hasattr(r, "_t_submit")
               for r in reqs)


def test_admitted_at_survives_a_preemption(smoke):
    """The pool of the scheduler's preemption test (15 pages of 8 for
    prompts of 16, 16 and 32 tokens with 20 new each) cannot hold all
    three at their peak: the youngest is preempted and admitted again,
    and keeps its first admission time."""
    cfg, params = smoke
    eng = _engine(cfg, params, slots=3, num_pages=15, decode_chunk=1)
    reqs = _requests(cfg, [16, 16, 32], [0.0] * 3, budget=20, seed=2)
    admissions = _pump_with_ticks(eng, reqs)
    bounced = [r for r in reqs if r.preemptions]
    assert bounced, "pool sized to force preemption never preempted"
    for r in bounced:
        first, *again = admissions[r.rid]
        assert again and again[0] > first
        assert r.admitted_at == first <= r.token_times[0]


def test_profiler_capture_holds_the_engine_spans(smoke, tmp_path):
    from jax.profiler import ProfileData

    cfg, params = smoke
    eng = _engine(cfg, params)
    reqs = _requests(cfg, [20, 12], [0.0, 0.0])
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.serve_trace(reqs)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
               dict(ev.stats))
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith("engine.")]
    names = {n for _, _, n, _ in events}
    assert names == {"engine." + p for p in HOST_PHASES}
    pumps = [(s, e) for s, e, n, _ in events if n == "engine.pump"]

    def inside_a_pump(s, e):
        return any(ps <= s and e <= pe for ps, pe in pumps)

    for s, e, n, ids in events:
        if n != "engine.pump":
            assert inside_a_pump(s, e), n
    admits = [ids for _, _, n, ids in events if n == "engine.admit"]
    assert {ids["rid"] for ids in admits} == {0, 1}
    prefills = [ids for _, _, n, ids in events if n == "engine.prefill"]
    # 20 and 12 tokens in quanta of 8: [0,8) [8,16) [16,20) and [0,8) [8,12)
    assert sorted((ids["rid"], ids["start"], ids["end"])
                  for ids in prefills) == [
        (0, 0, 8), (0, 8, 16), (0, 16, 20), (1, 0, 8), (1, 8, 12)]


@pytest.mark.parametrize("kernel", ["fusemax_decode_paged",
                                    "fusemax_mla_decode_paged",
                                    "fusemax_attention"])
def test_pallas_kernels_carry_their_names(kernel):
    b, h, e, t, ps = 2, 4, 64, 64, 16
    w = t // ps
    p = b * w
    s = jax.ShapeDtypeStruct
    if kernel == "fusemax_decode_paged":
        args = [s((b, h, 1, e), jnp.float32), s((p, h, ps, e), jnp.float32),
                s((p, h, ps, e), jnp.float32), s((b, w), jnp.int32),
                s((b,), jnp.int32)]
        fn = lambda q, k, v, bt, n: ops.fusemax_decode_paged(
            q, k, v, bt, n, impl="pallas", interpret=True)
    elif kernel == "fusemax_mla_decode_paged":
        r, rd = 64, 16
        args = [s((b, h, 1, r + rd), jnp.float32), s((p, ps, r), jnp.float32),
                s((p, ps, rd), jnp.float32), s((b, w), jnp.int32),
                s((b,), jnp.int32)]
        fn = lambda q, c, kr, bt, n: ops.fusemax_mla_decode_paged(
            q, c, kr, bt, n, impl="pallas", interpret=True)
    else:
        args = [s((1, h, t, e), jnp.float32)] * 3
        fn = lambda q, k, v: ops.fusemax_attention(
            q, k, v, causal=True, impl="pallas", interpret=True)

    def names(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"]
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", None)
                if inner is not None:
                    yield from names(getattr(inner, "jaxpr", inner))

    assert list(names(jax.make_jaxpr(fn)(*args).jaxpr)) == [kernel]
