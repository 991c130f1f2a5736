"""CPU tests of the benchmark: the yardstick's arithmetic, the traffic,
the registry, the reference against the engine, and the check that
decides ``correct`` failing on planted faults.  Smoke sizes only; no
number here is a device metric."""
from __future__ import annotations

import itertools
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, registry, traffic, window, work  # noqa: E402
from bench import trace as trace_mod  # noqa: E402
from bench.tests import tiny  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SEED = 2 ** 31 + 12345                   # larger than 32 signed bits hold
DENSE = registry.arch({"arch": "dense"})


# -- trace reduction -----------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "v5e_decode_prefill.xspace.pbtxt")) as f:
        text = f.read().split("\n", 1)[1]          # drop the comment line
    return ProfileData.from_text_proto(text)


def _events(pd, plane, line):
    p = pd.find_plane_with_name(plane)
    ln = next(x for x in p.lines if x.name == line)
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in ln.events]


def test_trace_busy_idle_and_programs(recorded):
    red = trace_mod.reduce(recorded, DENSE.KERNELS)
    win = _events(recorded, "/host:CPU", "python")
    (lo, hi, _), = [w for w in win if w[2] == "bench.window"]
    assert red.window_s == pytest.approx((hi - lo) * 1e-9)
    # busy: a millisecond grid, marked wherever an op runs
    ops = _events(recorded, "/device:TPU:0", "XLA Ops")
    grid = np.zeros(int((hi - lo) / 1e3) + 1, bool)       # 1 µs cells
    for s, e, _ in ops:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            grid[int((a - lo) / 1e3):int(np.ceil((b - lo) / 1e3))] = True
    assert red.busy_s == pytest.approx(grid.sum() * 1e-6, rel=0.02)
    assert red.idle_frac == pytest.approx(1 - red.busy_s / red.window_s)
    mods = _events(recorded, "/device:TPU:0", "XLA Modules")
    decode = sum(min(e, hi) - max(s, lo) for s, e, n in mods
                 if n.startswith("jit_loop("))
    prefill = sum(min(e, hi) - max(s, lo) for s, e, n in mods
                  if n.startswith("jit_prefill_into_slots("))
    assert red.programs["decode"] == pytest.approx(decode * 1e-9)
    assert red.programs["prefill"] == pytest.approx(prefill * 1e-9)
    assert red.program_runs == {"decode": 2, "prefill": 2}


def test_trace_kernels_and_breakdown(recorded):
    red = trace_mod.reduce(recorded, DENSE.KERNELS)
    ops = _events(recorded, "/device:TPU:0", "XLA Ops")
    mods = _events(recorded, "/device:TPU:0", "XLA Modules")

    def in_program(s, e, prefix):
        return any(ms <= s and e <= me and n.startswith(prefix)
                   for ms, me, n in mods)

    kernel = [(s, e) for s, e, n in ops if "tpu_custom_call" in n]
    want = sum(e - s for s, e in kernel if in_program(s, e, "jit_loop("))
    assert want > 0
    assert red.kernels["paged_decode"] == pytest.approx(want * 1e-9)
    assert red.kernels["prefill_attention"] > 0
    assert len(red.device_ops) == trace_mod.TOP
    times = [t for _, t in red.device_ops]
    assert times == sorted(times, reverse=True)
    assert all(name.startswith(("jit_loop/", "jit_prefill_into_slots/"))
               for name, _ in red.device_ops)
    # the longest idle gaps fall between dispatches, while the host pumps
    assert red.idle_gaps[0][1] > 1e-3
    assert {label for label, _ in red.idle_gaps[:3]} == {"bench.pump"}
    gaps = [t for _, t in red.idle_gaps]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= red.window_s - red.busy_s + 1e-9


# -- work counts ----------------------------------------------------------------

def test_work_hand_counts_one_layer():
    s = DENSE.Shapes(layers=1, d_model=8, heads=2, kv_heads=1, head_dim=4,
                     d_ff=16, vocab=10, itemsize=2)
    # q 8x8, k 8x4, v 8x4, o 8x8, gate/up 8x16 each, down 16x8
    assert s.layer_matmul_params == 64 + 32 + 32 + 64 + 3 * 128
    # one token reading 5 keys: 2·params + 4·5·heads·dh, + unembed 2·8·10
    assert s.decode_token_flops(5) == 2 * 576 + 4 * 5 * 2 * 4 + 160
    # positions 2,3,4 of a prompt read 3,4,5 keys
    assert s.prefill_flops(2, 5) == \
        2 * 576 * 3 + 4 * 2 * 4 * (3 + 4 + 5) + 160
    assert s.prefill_flops(5, 5) == 0
    # K and V of 5 positions of one kv head, q in and o out of 2 heads
    assert s.paged_decode_bytes(5) == (2 * 5 * 1 * 4 + 2 * 2 * 4) * 2
    assert s.paged_decode_flops(5) == 4 * 5 * 2 * 4
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_seconds(200, 50, peak) == 5.0
    assert work.roofline_seconds(2000, 5, peak) == 20.0


def test_work_shapes_from_configs():
    with open(os.path.join(ROOT, "bench", "configs",
                           "stablelm-1.6b.json")) as f:
        s = DENSE.Shapes.from_config(json.load(f))
    # 24 layers x 51.4M matmul weights (4 x 2048² + 3 x 2048 x 5632): the
    # published 1.64B less the two 205.5M embedding tables
    assert s.layers * s.layer_matmul_params == 24 * 51_380_224


# -- the window's arithmetic ------------------------------------------------------

def _steady_log(n=40, gap=0.25, tokens=20, tpot=0.01, stall_at=None,
                stall=0.0):
    """Requests every ``gap`` s, each served 10 ms after arrival and then
    a token every ``tpot`` s; a stall of ``stall`` s from ``stall_at``
    delays every token due after it."""
    recs = []
    for i in range(n):
        a = i * gap
        times = []
        for k in range(tokens):
            t = a + 0.01 + k * tpot
            if stall_at is not None and t >= stall_at:
                t += stall
            times.append(t)
        recs.append(window.Record(arrival=a, token_times=times,
                                  expected=tokens))
    return recs


def test_window_stall_moves_rate_and_both_tails():
    base = window.window_metrics(_steady_log(tokens=60), 0.0, 10.0)
    hit = window.window_metrics(
        _steady_log(tokens=60, stall_at=5.1, stall=1.0), 0.0, 10.0)
    assert hit["tok_per_s"] < base["tok_per_s"]
    assert hit["ttft_p95_ms"] > base["ttft_p95_ms"] + 100
    assert hit["tpot_p95_ms"] > base["tpot_p95_ms"] * 2
    # the medians barely see a one-second stall in ten
    assert hit["ttft_p50_ms"] == pytest.approx(base["ttft_p50_ms"])
    assert hit["tpot_p50_ms"] == pytest.approx(base["tpot_p50_ms"])
    # chunked delivery: pooled gaps are 0 for 15 of 16 tokens, so their
    # median is 0 with or without the stall; tpot is not
    gaps = [b - a for r in _steady_log(tpot=0.0) for a, b in
            zip(r.token_times, r.token_times[1:])]
    assert np.median(gaps) == 0.0


def test_window_counts_only_what_is_due_and_delivered_inside():
    recs = _steady_log(n=4, gap=1.0, tokens=3)
    recs.append(window.Record(arrival=9.9, token_times=[10.5, 10.6],
                              expected=2))          # due, served after
    recs.append(window.Record(arrival=11.0, token_times=[11.1],
                              expected=1))          # not due
    recs.append(window.Record(arrival=2.0, token_times=[2.5],
                              expected=3))          # never finished
    m = window.window_metrics(recs, 0.0, 10.0)
    assert m["attempted"] == 6
    assert m["failed"] == 1
    assert m["tokens_in_window"] == 4 * 3 + 1
    assert m["tok_per_s"] == pytest.approx(1.3)


# -- traffic --------------------------------------------------------------------

def _mix(name):
    with open(os.path.join(ROOT, "bench", "traffic", name + ".json")) as f:
        return json.load(f)


#: an open-loop mix with shared system prompts, as a chat cell would run
CHAT = {"loop": "open", "rate_per_s": 1.0, "block": 32,
        "shared_prefix": {"count": 8, "tokens": 512, "zipf_s": 1.0},
        "prompt": {"min": 64, "max": 383, "median": 160, "sigma": 0.5},
        "output": {"min": 32, "max": 128, "median": 64, "sigma": 0.5}}


def test_traffic_is_deterministic_per_seed():
    p = CHAT                 # 32 s at 1 req/s: one whole block of 32
    a = traffic.Mix(p, 49155, 1024, SEED).open_requests(32.0)
    b = traffic.Mix(p, 49155, 1024, SEED).open_requests(32.0)
    c = traffic.Mix(p, 49155, 1024, SEED + 1).open_requests(32.0)
    assert [x.arrival_s for x in a] == [x.arrival_s for x in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    # another seed: the same work in the same order, other tokens
    assert [len(x.prompt) for x in a] == [len(x.prompt) for x in c]
    assert [x.output_len for x in a] == [x.output_len for x in c]
    assert [x.arrival_s for x in a] == [x.arrival_s for x in c]
    assert [x.prefix_id for x in a] == [x.prefix_id for x in c]
    assert not any((x.prompt == y.prompt).all() for x, y in zip(a, c))
    assert max(x.arrival_s for x in a) < 32.0
    dh = _mix("decode-heavy")

    def closed(seed):
        return list(itertools.islice(traffic.Mix(
            dh, 100352, 1024, seed).closed_requests(), 64))
    d, e, f = closed(SEED), closed(SEED), closed(SEED + 1)
    assert all((x.prompt == y.prompt).all() and x.output_len == y.output_len
               for x, y in zip(d, e))
    assert all(16 <= len(x.prompt) <= 256 and 16 <= x.output_len <= 767
               and len(x.prompt) + x.output_len < 1024 for x in d)
    assert [(len(x.prompt), x.output_len) for x in d] == \
        [(len(x.prompt), x.output_len) for x in f]
    assert not any((x.prompt == y.prompt).all() for x, y in zip(d, f))
    # every block of 16 holds the same quantiles, each in its own order
    for i in range(16, 64, 16):
        assert sorted(len(x.prompt) for x in d[i:i + 16]) == \
            sorted(len(x.prompt) for x in d[:16])
        assert sorted(x.output_len for x in d[i:i + 16]) == \
            sorted(x.output_len for x in d[:16])
    assert [x.output_len for x in d[:16]] != [x.output_len for x in d[16:32]]
    # the mix's stated means (the source's) hold for each block
    assert np.mean([len(x.prompt) for x in d[:16]]) == \
        pytest.approx(161.31, abs=1.0)
    assert np.mean([x.output_len for x in d[:16]]) == \
        pytest.approx(337.99, abs=1.0)


def test_prefix_chat_makes_only_the_named_prefill_offsets():
    p = CHAT
    items = traffic.Mix(p, 49155, 1024, SEED).open_requests(60.0)
    offsets = set()
    for it in items:
        assert 512 + 64 <= len(it.prompt) <= 512 + 384
        assert len(it.prompt) + it.output_len < 1024
        for cached in (0, 256, 512):       # cold, mid-prefill hit, hit
            offsets |= {o for o, _ in harness.prefill_keys(
                len(it.prompt), cached, 256, 1024)}
    assert offsets == {0, 256, 512, 768}
    ids = [it.prefix_id for it in items]
    assert ids.count(0) > ids.count(1) > ids.count(7) > 0   # Zipf


def test_warm_trace_reaches_every_prefill_key():
    p = CHAT
    mix = traffic.Mix(p, 49155, 1024, SEED)
    keys = lambda n, c: harness.prefill_keys(n, c, 256, 1024)
    warm = mix.warm_requests(keys, SEED ^ harness.WARM_SALT)
    got = set()
    for i, it in enumerate(warm):
        cached = 512 if i and it.prefix_id == 0 else 0
        got |= keys(len(it.prompt), cached)
    want = set()
    for n in mix.prompt_lengths():
        want |= keys(n, 0) | keys(n, 512)
    assert got == want
    assert len(warm) < 30
    assert not any((w.prompt[:512] == mix.prefixes[0]).all() for w in warm)


# -- registry ---------------------------------------------------------------------

def test_registry_finds_new_pieces_from_files_alone(tmp_path):
    root = tiny.make_root(str(tmp_path), extra_metrics={
        "throwaway": "def read(rec):\n    return rec['stats']['x'] / 2\n",
        "silent": "def read(rec):\n    return None\n"})
    cell = registry.load_cell(root, "tiny.chat")
    assert cell.config["hidden_size"] == 64
    assert cell.arch.KERNELS == DENSE.KERNELS
    assert cell.mix["loop"] == "open"
    assert cell.checks == {"max_logit_gap": 1e9, "mean_logit_gap": 1e9}
    assert [m["name"] for m in cell.end_to_end] == [
        "tok_per_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s"]
    got = registry.read_metrics(cell, {"stats": {"x": 3}})
    assert got == {"throwaway": {"value": 1.5, "unit": "frac"}}
    with pytest.raises(KeyError):
        registry.load_cell(root, "no.such.cell")
    with pytest.raises(KeyError):
        registry.peaks("TPU v0 imaginary")
    assert registry.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_benchmark_json_names_files_that_exist():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for w in spec["workloads"]:
        cell = registry.load_cell(ROOT, w["name"])
        assert cell.mix["loop"] in ("open", "closed")
        assert cell.checks["max_logit_gap"] > 0
        assert cell.checks["mean_logit_gap"] > 0
        for m in cell.per_layer:
            assert callable(registry.metric_reader(m["name"]))


# -- the command without a chip ---------------------------------------------------

def test_run_without_tpu_prints_nothing_and_fails(capsys):
    from bench import run

    assert run.main(["--workload", "stablelm-1.6b.decode-heavy", "--seed",
                     str(SEED), "--seconds", "1", "--trace", "0"]) != 0
    assert capsys.readouterr().out == ""


def test_run_without_the_program_fails(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "out"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "stablelm-1.6b.decode-heavy", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""


# -- the reference against the engine -------------------------------------------

def _program_f32(config, seed):
    import jax
    import jax.numpy as jnp

    from repro.model import transformer as tf
    from repro.model.layers import Runtime

    cfg = registry.arch(config).model_config(config)
    rt = Runtime(attn_impl="jnp", param_dtype=jnp.bfloat16,
                 activation_dtype=jnp.float32)
    # weights drawn and rounded as served (bf16), then run in float32
    params = jax.jit(lambda k: jax.tree.map(
        lambda a: a.astype(jnp.float32), tf.init(cfg, k, rt)[0]))(
        jax.random.PRNGKey(seed))
    return cfg, rt, params


@pytest.mark.parametrize("norm,kv_heads", [("layernorm", 4),
                                           ("rmsnorm", 2)])
def test_reference_matches_the_engine(norm, kv_heads):
    """Prefill then paged decode through the engine (float32, prefix
    cache on, the second and third prompts hitting the first one's
    pages) against the reference's full forward of the same tokens."""
    import jax
    import jax.numpy as jnp

    from bench import reference
    from repro.serving.scheduler import AsyncRequest, AsyncServeEngine

    config = dict(tiny.TINY_CONFIG, norm=norm, num_key_value_heads=kv_heads,
                  tie_word_embeddings=norm == "rmsnorm")
    cfg, rt, params = _program_f32(config, SEED)
    # the program's own full forward agrees with the reference closely
    toks = np.random.default_rng(0).integers(0, 256, (2, 48), np.int32)
    from repro.model import transformer as tf
    with jax.default_matmul_precision("highest"):
        want = np.asarray(tf.forward(cfg, params, {"inputs": jnp.asarray(
            toks)}, rt))
    got = reference.logits_at(registry.arch(config), config, SEED,
                              list(toks), [np.arange(48)] * 2, 64)
    np.testing.assert_allclose(np.stack(got), want, atol=2e-3, rtol=2e-3)

    eng = AsyncServeEngine(cfg, params, slots=4, max_len=128, rt=rt,
                           dtype=jnp.float32, decode_chunk=4,
                           cache_layout="paged", page_size=16,
                           prefix_caching=True, prefill_quantum=32)
    shared = toks[0, :32]
    rng = np.random.default_rng(1)
    reqs = [AsyncRequest(rid=i, prompt=np.concatenate(
                [shared, rng.integers(0, 256, 20 + 5 * i).astype(np.int32)]),
                max_new_tokens=12, arrival=0.0) for i in range(3)]
    eng.serve_trace(reqs[:1])
    eng.serve_trace(reqs[1:])
    assert eng.stats["tokens_reused"] >= 2 * 32
    gaps = harness.check(registry.arch(config), config, SEED, reqs,
                         128)["program"]
    assert gaps.size == 36
    assert gaps.max() < 1e-3


# -- correct, and its failure on planted faults ----------------------------------

def _tamper_token(eng):
    """A token altered where it is produced: the decode program's tokens
    reach the host shifted by one id."""
    get = eng._get_loop

    def loop(n):
        fn = get(n)

        def altered(*a):
            out = fn(*a)
            return ((out[0] + 1) % eng.cfg.vocab,) + tuple(out[1:])
        return altered
    eng._get_loop = loop


def _tamper_state(eng):
    """A step that returns its state unchanged: the decode program's K/V
    writes never reach the pool."""
    import jax

    from repro.model import transformer as tf

    def loop(n):
        def body(params, caches, kv_len, logits, remaining, key, tables):
            return tf.decode_loop(eng.cfg, params, caches, kv_len, logits,
                                  remaining, key, n_steps=n, rt=eng.rt,
                                  block_tables=tables)
        fn = jax.jit(body)

        def frozen(params, caches, *rest):
            out = fn(params, caches, *rest)
            return (out[0], caches) + tuple(out[2:])
        return frozen
    eng._get_loop = loop


#: the tiny cells' limits, set from CPU readings at this size: sound runs
#: read a widest gap of at most 0.11 and a mean gap of 0 (four seeds), the
#: float8 control a widest gap of 0.73 and a mean of 0.023 and more
TINY_LIMIT = 0.3
TINY_MEAN_LIMIT = 0.01


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("tiny")),
                          gap_limit=TINY_LIMIT, mean_limit=TINY_MEAN_LIMIT)


@pytest.mark.parametrize("seed", [SEED, 11])
def test_control_fails_the_limit(tiny_root, seed):
    """The reference in float8, put in the program's place, comes out as
    not correct through the run's own check, on both gaps, where sound
    runs of the program stay under the limits."""
    out = harness.run(tiny_root, "tiny.chat", seed, 1.0, False,
                      harness.Options(interpret=True, control=True))
    assert out["correct"] is False
    got, checks = out["readings"], out["checks"]
    assert got["program"]["max"] < TINY_LIMIT < got["fp8"]["max"]
    assert got["program"]["mean"] < TINY_MEAN_LIMIT < got["fp8"]["mean"]
    assert checks["max_logit_gap"]["value"] == got["fp8"]["max"]
    assert checks["mean_logit_gap"]["value"] == got["fp8"]["mean"]
    assert checks["kv_bits_short"]["value"] == 0


def test_program_fp8_kv_pages_fail_the_check(tiny_root):
    """The program's own lower-precision path, float8 K/V pages, comes
    out as not correct: its pool holds fewer bits per element than the
    bfloat16 the configuration states."""
    out = harness.run(tiny_root, "tiny.chat", SEED, 1.0, False,
                      harness.Options(interpret=True,
                                      engine={"kv_dtype": "fp8_e4m3"}))
    assert out["correct"] is False
    # 8 bits of data and a 16-bit scale per token and head of 16 elements
    assert out["checks"]["kv_bits_short"]["value"] == pytest.approx(7.0)


@pytest.mark.parametrize("fault", [None, "token", "state"])
def test_correct_fails_on_planted_faults(tiny_root, fault):
    tamper = {None: None, "token": _tamper_token,
              "state": _tamper_state}[fault]
    out = harness.run(tiny_root, "tiny.chat", SEED, 1.0, False,
                      harness.Options(interpret=True, tamper=tamper))
    gap = out["checks"]["max_logit_gap"]["value"]
    assert out["checks"]["kv_bits_short"]["value"] == 0
    assert out["attempted"] > 5 and out["failed"] == 0
    assert set(out["metrics"]) == {"tok_per_s", "ttft_p95_ms",
                                   "tpot_p95_ms", "setup_s"}
    assert list(out)[-1] == "checks"
    assert out["correct"] is (fault is None), gap
