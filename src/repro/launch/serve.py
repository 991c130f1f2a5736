"""Serving driver: continuous-batching engine over the FuseMax decode path.

  python -m repro.launch.serve --arch gemma2-9b-smoke --requests 6 \
      --slots 4 --max-len 256 --cache-layout both

Runs the device-resident fast path (bucketed batched prefill + fused
multi-step decode) and writes ``BENCH_serving.json`` — tok/s,
time-to-first-token, steps/s, dispatch counts, and cache-memory residency
— so the serving perf trajectory is tracked across PRs (see
EXPERIMENTS.md).

``--cache-layout both`` serves the same trace through the dense and the
paged layout and cross-checks that greedy outputs are identical
(``outputs_match``); ``--prompt-len-max`` makes the trace mixed-length
(uniform in [prompt-len, prompt-len-max]) — the workload where the paged
layout's resident bytes pull away from the dense layout's slots×max_len.
``--shared-prefix-len N`` gives every prompt the same N-token head
(system-prompt traffic): the paged engine's automatic prefix cache serves
the head from resident pages and prefills only the tails — the bench
reports hit rate / tokens reused / COW copies / prefill-dispatch savings
and additionally cross-checks greedy outputs against a paged engine with
the prefix cache disabled.
``--mesh tp=N`` additionally serves the trace with the paged pool
*device-sharded* over an N-way mesh (kv-head / latent-rank partitioning,
``paged_sharded`` layout) — outputs_match then asserts sharded ==
single-device greedy streams and ``memory.sharding.per_device`` reports
the 1/tp residency.  On CPU set
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` first.
``--speculate k`` turns on speculative decoding (greedy-only): the
engine's n-gram proposer drafts k tokens per slot and a single fused
verify dispatch scores the whole chain (see
:mod:`repro.serving.speculate`).  Every requested layout then serves the
trace speculatively, one extra ``<layout>_nospec`` leg serves it without
speculation on the *identical* trace for the speedup ratio, and
outputs_match asserts the greedy streams are bit-identical either way.
``--duplicates N`` appends N duplicate requests (cycling over the
originals) to the trace — the popular/repeated-query traffic where
cross-request drafting shines: a duplicate whose original already
completed drafts from the original's indexed stream and verifies
near-perfectly.  The proposer's n-gram table is cleared between
``--repeats`` (like the prefix index) so a warm table can't memorize the
re-served trace and report fake acceptance.
``--pool-mb M`` sizes the paged pool by a *byte* budget instead of a
page count (num_pages = budget // bytes_per_page, so a cheaper page
dtype honestly buys capacity).  ``--kv-dtype fp8_e4m3|int8`` stores the
K/V (and MLA latent) pages quantized with parallel fp16 per-token scale
pools, served as an extra ``paged_quant`` leg that is *excluded* from
outputs_match — its greedy drift vs the full-width ``paged`` leg is
reported under ``quant_quality`` instead.  ``--host-swap-gb G`` adds a
host-RAM swap tier under the prefix index (LRU evictions demote pages
to host, prefix hits promote them back; ``paged_swap`` leg, lossless
and therefore *inside* outputs_match) — see EXPERIMENTS.md
"Quantized KV pages + host-memory swap tier".
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.model import transformer as tf
from repro.model.layers import Runtime
from repro.serving.engine import (
    Request, ServeEngine, enable_compilation_cache,
)
from repro.serving.scheduler import (
    AsyncRequest, AsyncServeEngine, DataParallelAsyncEngine, WallClock,
    latency_metrics, poisson_arrivals, serve_open_loop,
)


def _trace_lens(args) -> list:
    rng = np.random.default_rng(args.seed)
    hi = args.prompt_len_max
    if hi is None or hi <= args.prompt_len:
        lens = [args.prompt_len] * args.requests
    else:
        lens = [int(x) for x in
                rng.integers(args.prompt_len, hi + 1, size=args.requests)]
    if args.shared_prefix_len:
        # every prompt carries the shared prefix plus ≥ 1 distinct token
        lens = [max(p, args.shared_prefix_len + 1) for p in lens]
    return lens


def _parse_mesh(arg: Optional[str]):
    """``--mesh tp=N`` → a 1-axis ("model",) mesh of N devices (the paged
    pool shards over it).  None/empty/tp=1 → no mesh."""
    if not arg:
        return None
    try:
        key, n = arg.split("=")
        n = int(n)
    except ValueError:
        raise SystemExit(f"--mesh expects tp=N, got {arg!r}")
    if key != "tp":
        raise SystemExit(f"--mesh expects tp=N, got {arg!r}")
    if n <= 1:
        return None
    if n > jax.device_count():
        raise SystemExit(
            f"--mesh tp={n} needs {n} devices but only "
            f"{jax.device_count()} are visible (CPU smoke: set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} "
            f"before jax imports)")
    from repro.launch.mesh import make_mesh
    return make_mesh((n,), ("model",))


def _serve_one_layout(args, cfg, params, rt, layout: str,
                      prefix_caching: bool = True, mesh=None,
                      speculate: Optional[int] = None,
                      kv_dtype: Optional[str] = None,
                      host_swap_bytes: int = 0) -> dict:
    pool_bytes = None
    if layout == "paged" and getattr(args, "pool_mb", None):
        # byte-denominated pool budget: quantized legs get proportionally
        # more pages out of the same budget — the honest capacity A/B
        pool_bytes = int(args.pool_mb * (1 << 20))
    engine = ServeEngine(cfg, params, slots=args.slots,
                         max_len=args.max_len, rt=rt,
                         temperature=args.temperature,
                         decode_chunk=args.decode_chunk,
                         prefill_chunk=args.prefill_chunk,
                         cache_layout=layout,
                         page_size=args.page_size,
                         num_pages=args.num_pages,
                         prefix_caching=prefix_caching,
                         speculate=speculate,
                         kv_dtype=kv_dtype,
                         pool_bytes=pool_bytes,
                         host_swap_bytes=host_swap_bytes,
                         mesh=mesh)
    lens = _trace_lens(args)
    warmup_s = None
    if not args.no_warmup:
        warmup_s = round(engine.warmup(sorted(set(lens))), 4)

    # median-of-N traces (the kernel-bench timing protocol): smoke traces
    # finish in ~0.1s, where single-shot wall clocks are noise
    runs = []
    for _ in range(max(1, args.repeats)):
        for k in engine.stats:
            engine.stats[k] = 0
        # each repeat serves the identical trace, so a warm index would
        # fully absorb runs 2..N (hit_rate → 1.0) and the median run
        # would report same-trace rerun reuse instead of the advertised
        # shared-prefix reuse; clearing keeps repeats homogeneous (the
        # tail-offset jit keys still compile only once, in run 1, so the
        # median of ≥ 3 repeats excludes the compile cost)
        engine.clear_prefix_cache()
        if engine.proposer is not None:
            # same trap as the prefix index: a warm n-gram table would
            # absorb runs 2..N of the identical trace and report
            # same-trace-rerun acceptance instead of the advertised
            # duplicate-traffic acceptance
            engine.proposer.clear()
        rng = np.random.default_rng(args.seed)
        sp = args.shared_prefix_len
        shared = rng.integers(0, cfg.vocab, size=(sp,)) if sp else None
        t0 = time.perf_counter()
        reqs = []
        for rid, plen in enumerate(lens):
            prompt = rng.integers(0, cfg.vocab, size=(plen - sp,)) if sp \
                else rng.integers(0, cfg.vocab, size=(plen,))
            if sp:
                prompt = np.concatenate([shared, prompt])
            req = Request(rid=rid, prompt=prompt.astype(np.int32),
                          max_new_tokens=args.new_tokens)
            reqs.append(req)
            engine.submit(req)
        for j in range(getattr(args, "duplicates", 0) or 0):
            # duplicate traffic: resend earlier prompts verbatim (FIFO
            # admission means a duplicate typically enters after its
            # original completed — the cross-request drafting workload)
            src = reqs[j % len(lens)]
            req = Request(rid=len(lens) + j, prompt=src.prompt.copy(),
                          max_new_tokens=args.new_tokens)
            reqs.append(req)
            engine.submit(req)
        engine.run()
        runs.append((time.perf_counter() - t0, dict(engine.stats), reqs))
    runs.sort(key=lambda r: r[0])
    dt, stats, reqs = runs[len(runs) // 2]
    engine.stats.update(stats)

    total_new = sum(len(r.generated) for r in reqs)
    prompt_tokens = sum(len(r.prompt) for r in reqs)
    ttfts = [r.ttft for r in reqs if r.ttft is not None]
    out = {
        "cache_layout": layout,
        "prefix_caching": prefix_caching and engine.kv is not None
            and engine.kv.prefix_enabled,
        "prefix": {
            "hits": stats["prefix_hits"],
            "hit_rate": round(stats["prefix_hits"] / len(reqs), 3),
            "tokens_reused": stats["tokens_reused"],
            "cow_copies": stats["cow_copies"],
            "tokens_prefilled": stats["tokens_prefilled"],
            "prompt_tokens": prompt_tokens,
            # fraction of prompt tokens whose prefill dispatch was skipped
            "prefill_savings": round(
                1.0 - stats["tokens_prefilled"] / max(prompt_tokens, 1),
                3),
        },
        "warmup_s": warmup_s,
        "wall_s": round(dt, 4),
        "tok_per_s": round(total_new / dt, 2),
        "ttft_s": {
            "mean": round(float(np.mean(ttfts)), 4) if ttfts else None,
            "p50": round(float(np.median(ttfts)), 4) if ttfts else None,
            "max": round(float(np.max(ttfts)), 4) if ttfts else None,
        },
        "steps_per_s": round(engine.stats["decode_steps"] / dt, 2),
        "dispatches": {
            "prefill": engine.stats["prefill_dispatches"],
            "decode": engine.stats["decode_dispatches"],
            "decode_steps": engine.stats["decode_steps"],
        },
        "tokens_decoded": engine.stats["tokens_decoded"],
        "preemptions": engine.stats["preemptions"],
        "peak_live_tokens": engine.stats["peak_live_tokens"],
        "memory": engine.memory_stats(),
        "_outputs": [list(r.generated) for r in reqs],
    }
    if engine.spec_k is not None:
        out["speculation"] = {
            "k": engine.spec_k,
            "dispatches": stats["spec_dispatches"],
            "proposed": stats["spec_proposed"],
            "accepted": stats["spec_accepted"],
            "accept_rate": round(
                stats["spec_accepted"] / max(1, stats["spec_proposed"]),
                3),
            # committed tokens per model evaluation (every decode
            # dispatch, spec or not, is one evaluation) — the number
            # that has to beat 1.0 for speculation to pay
            "accepted_per_dispatch": round(
                stats["tokens_decoded"] /
                max(1, stats["decode_dispatches"]), 3),
        }
    return out


def _async_trace(args, cfg) -> tuple:
    """The open-loop trace: (prompts, decode budgets).  The usual seeded
    trace (shared prefix / mixed lengths supported), with every
    ``--long-every``-th request replaced by a ``--long-prompt-len``
    prompt with its own ``--long-new-tokens`` budget — the
    chat-plus-batch mix where short interactive streams decode for a
    long time while long-prompt jobs keep arriving, and a synchronous
    engine's whole-prompt admission prefill stalls every in-flight
    stream (the interleave stress case)."""
    rng = np.random.default_rng(args.seed)
    lens = _trace_lens(args)
    budgets = [args.new_tokens] * len(lens)
    long_len = getattr(args, "long_prompt_len", 0) or 0
    if long_len:
        k = max(2, getattr(args, "long_every", 3) or 3)
        long_new = getattr(args, "long_new_tokens", None) \
            or args.new_tokens
        for i in range(len(lens)):
            if i % k == k - 1:
                lens[i] = long_len
                budgets[i] = long_new
    sp = args.shared_prefix_len
    shared = rng.integers(0, cfg.vocab, size=(sp,)) if sp else None
    prompts = []
    for plen in lens:
        tail = rng.integers(0, cfg.vocab, size=(plen - sp,)) if sp \
            else rng.integers(0, cfg.vocab, size=(plen,))
        prompts.append(
            (np.concatenate([shared, tail]) if sp else tail)
            .astype(np.int32))
    return prompts, budgets


def _fresh_requests(prompts, budgets, arrivals, t0) -> list:
    return [AsyncRequest(rid=i, prompt=p.copy(), max_new_tokens=int(b),
                         arrival=t0 + float(a))
            for i, (p, b, a) in enumerate(zip(prompts, budgets,
                                              arrivals))]


def _async_engine(args, cfg, params, rt, *, layout, prefix_caching,
                  clock=None, mesh=None) -> AsyncServeEngine:
    return AsyncServeEngine(
        cfg, params, slots=args.slots, max_len=args.max_len, rt=rt,
        temperature=args.temperature, decode_chunk=args.decode_chunk,
        prefill_chunk=args.prefill_chunk, cache_layout=layout,
        page_size=args.page_size, num_pages=args.num_pages,
        prefix_caching=prefix_caching,
        prefill_quantum=getattr(args, "prefill_quantum", None),
        clock=clock, mesh=mesh)


def _leg_summary(engine, reqs) -> dict:
    out = latency_metrics(reqs)
    out["dispatches"] = {
        "prefill": engine.stats["prefill_dispatches"],
        "decode": engine.stats["decode_dispatches"],
        "decode_steps": engine.stats["decode_steps"],
    }
    out["preemptions"] = engine.stats["preemptions"]
    out["tokens_reused"] = engine.stats["tokens_reused"]
    return out


def serve_async_bench(args) -> dict:
    """Open-loop async serving bench: the same seeded Poisson arrival
    trace served through (a) the async engine on dense / paged /
    paged+prefix — greedy streams asserted bit-identical to a
    synchronous reference engine (``outputs_match``), (b) a *timed*
    async vs sync-open-loop A/B on the paged+prefix layout for the
    tail-latency comparison (``itl_p95_sync_over_async`` — the
    interleaved-prefill win), and (c, ``--dp N``) N replicas behind the
    prefix-affinity router for the routed cache-hit multiplier."""
    if getattr(args, "speculate", None) and not getattr(
            args, "no_speculate", False):
        raise SystemExit("--speculate does not combine with --async yet "
                         "(the fused verify dispatch conflicts with "
                         "mid-prefill slots)")
    cfg = get_config(args.arch)
    rt = Runtime(attn_impl="auto", activation_dtype=jnp.float32,
                 param_dtype=jnp.float32)
    params, _ = tf.init(cfg, jax.random.PRNGKey(args.seed), rt)
    prompts, budgets = _async_trace(args, cfg)
    lens = sorted({len(p) for p in prompts})
    arr = poisson_arrivals(args.arrival_rate, len(prompts),
                           seed=args.seed)

    # -- bit-equality legs: async dense / paged / paged+prefix, plus the
    # synchronous reference on the identical request set.  Scheduling
    # changes when a token is computed, never what, so every greedy
    # stream must be byte-for-byte the sync engine's.
    outputs = {}
    legs = {"dense": ("dense", False),
            "paged_noprefix": ("paged", False),
            "paged": ("paged", True)}
    timed = {}
    for name, (layout, prefix) in legs.items():
        eng = _async_engine(args, cfg, params, rt, layout=layout,
                            prefix_caching=prefix)
        warm = None
        if not args.no_warmup:
            warm = round(eng.warmup(lens), 4)
        reqs = _fresh_requests(prompts, budgets, arr, eng.clock.now())
        eng.serve_trace(reqs)
        outputs[name] = [list(r.generated) for r in reqs]
        timed[name] = _leg_summary(eng, reqs)
        timed[name]["warmup_s"] = warm
        timed[name]["interleave"] = eng.interleave

    sync_ref = ServeEngine(
        cfg, params, slots=args.slots, max_len=args.max_len, rt=rt,
        temperature=args.temperature, decode_chunk=args.decode_chunk,
        prefill_chunk=args.prefill_chunk, cache_layout="paged",
        page_size=args.page_size, num_pages=args.num_pages,
        prefix_caching=True)
    if not args.no_warmup:
        sync_ref.warmup(lens)
    sync_clock = WallClock()
    sreqs = _fresh_requests(prompts, budgets, arr, sync_clock.now())
    serve_open_loop(sync_ref, sreqs, clock=sync_clock)
    outputs["sync"] = [list(r.generated) for r in sreqs]
    outputs_match = all(outputs[n] == outputs["sync"] for n in legs)
    sync_lat = latency_metrics(sreqs)

    a = timed["paged"]
    ratio = None
    if a["itl_s"]["p95"] and sync_lat["itl_s"]["p95"]:
        ratio = round(sync_lat["itl_s"]["p95"] / a["itl_s"]["p95"], 3)

    metrics = {
        "arch": args.arch,
        "mode": "async_open_loop",
        "requests": len(prompts),
        "slots": args.slots,
        "arrival_rate": args.arrival_rate,
        "seed": args.seed,
        "prompt_len": args.prompt_len,
        "long_prompt_len": getattr(args, "long_prompt_len", 0) or 0,
        "long_every": getattr(args, "long_every", 3) or 3,
        "shared_prefix_len": args.shared_prefix_len,
        "new_tokens": args.new_tokens,
        "decode_chunk": args.decode_chunk,
        "prefill_quantum": getattr(args, "prefill_quantum", None)
            or (args.prefill_chunk or 32),
        "page_size": args.page_size,
        "outputs_match": outputs_match,
        "async": a,
        "async_legs": timed,
        "sync_open_loop": sync_lat,
        "itl_p95_sync_over_async": ratio,
        # the generic regression gate reads these two top-level fields
        "tok_per_s": a["tok_per_s"],
        "ttft_s": a["ttft_s"],
    }

    dp = getattr(args, "dp", 1) or 1
    if dp > 1:
        from repro.launch.mesh import make_replica_meshes
        tp = 1
        mesh_arg = getattr(args, "mesh", None)
        if mesh_arg:
            m = _parse_mesh(mesh_arg)
            tp = int(m.shape["model"]) if m is not None else 1
        meshes = make_replica_meshes(dp, tp)
        clock = WallClock()
        engines = []
        for i in range(dp):
            e = _async_engine(args, cfg, params, rt, layout="paged",
                              prefix_caching=True, clock=clock,
                              mesh=meshes[i])
            if not args.no_warmup:
                e.warmup(lens)
            engines.append(e)
        dpe = DataParallelAsyncEngine(engines)
        # arrival-time routing is the point: the prefix index evolves as
        # earlier requests prefill, so a lower rate gives each arrival a
        # registered prefix to match (the router is still exercised cold
        # on the first request)
        dp_rate = getattr(args, "dp_arrival_rate", None) \
            or args.arrival_rate
        dp_arr = poisson_arrivals(dp_rate, len(prompts), seed=args.seed)
        dreqs = _fresh_requests(prompts, budgets, dp_arr, clock.now())
        dpe.serve_trace(dreqs)
        dp_out = [list(r.generated) for r in dreqs]
        metrics["dp"] = dict(
            dpe.stats_summary(),
            tp=tp,
            arrival_rate=dp_rate,
            latency=latency_metrics(dreqs),
            outputs_match=dp_out == outputs["sync"],
        )
        metrics["outputs_match"] = outputs_match and \
            metrics["dp"]["outputs_match"]
    return metrics


def serve_bench(args) -> dict:
    """Build engine(s), serve the synthetic trace, return the metrics."""
    cfg = get_config(args.arch)
    rt = Runtime(attn_impl="auto", activation_dtype=jnp.float32,
                 param_dtype=jnp.float32)
    params, _ = tf.init(cfg, jax.random.PRNGKey(args.seed), rt)

    layouts = ["dense", "paged"] if args.cache_layout == "both" \
        else [args.cache_layout]
    mesh = _parse_mesh(getattr(args, "mesh", None))
    if mesh is not None and "paged" not in layouts:
        raise SystemExit("--mesh shards the paged pool; add "
                         "--cache-layout paged (or both)")
    spec = None if getattr(args, "no_speculate", False) \
        else getattr(args, "speculate", None)
    if spec is not None:
        from repro.serving.engine import speculation_supported
        if args.temperature > 0:
            raise SystemExit("--speculate is greedy-only: the accept rule "
                             "reproduces the non-speculative stream only "
                             "at temperature 0")
        if mesh is not None:
            raise SystemExit("--speculate does not combine with --mesh "
                             "(the verify kernels run unsharded)")
        if not speculation_supported(cfg):
            raise SystemExit(
                f"--speculate unsupported for {args.arch}: needs every "
                f"layer to be global GQA/MLA attention + dense MLP")
    per_layout = {lo: _serve_one_layout(
        args, cfg, params, rt, lo,
        prefix_caching=not args.no_prefix_cache,
        speculate=spec) for lo in layouts}
    if args.shared_prefix_len and "paged" in layouts \
            and not args.no_prefix_cache:
        # shared-prefix trace mode: A/B the paged layout with the prefix
        # cache disabled too — greedy streams must be identical either way
        per_layout["paged_noprefix"] = _serve_one_layout(
            args, cfg, params, rt, "paged", prefix_caching=False,
            speculate=spec)
        layouts = layouts + ["paged_noprefix"]
    if spec is not None:
        # speculation A/B: serve the identical trace once more WITHOUT
        # speculation on the primary paged layout — outputs_match then
        # asserts spec == non-spec greedy streams, and the tok/s ratio is
        # the honest speedup (same trace, same layout, same warmup)
        base_lo = "paged" if "paged" in layouts else layouts[0]
        per_layout[base_lo + "_nospec"] = _serve_one_layout(
            args, cfg, params, rt, base_lo,
            prefix_caching=not args.no_prefix_cache)
        layouts = layouts + [base_lo + "_nospec"]
    if mesh is not None:
        # device-sharded pool: serve the identical trace once more with
        # the pool partitioned over the mesh — outputs_match then covers
        # sharded vs single-device, and memory.sharding.per_device shows
        # the 1/tp residency
        per_layout["paged_sharded"] = _serve_one_layout(
            args, cfg, params, rt, "paged",
            prefix_caching=not args.no_prefix_cache, mesh=mesh)
        layouts = layouts + ["paged_sharded"]
    swap_bytes = int((getattr(args, "host_swap_gb", 0) or 0) * (1 << 30))
    if swap_bytes and "paged" in per_layout:
        # host swap tier is lossless (pages round-trip bit-exact through
        # host RAM), so this leg joins outputs_match
        per_layout["paged_swap"] = _serve_one_layout(
            args, cfg, params, rt, "paged",
            prefix_caching=not args.no_prefix_cache, speculate=spec,
            host_swap_bytes=swap_bytes)
        layouts = layouts + ["paged_swap"]
    quant_leg = None
    if getattr(args, "kv_dtype", None) and "paged" in per_layout:
        # quantized pages change numerics, so this leg is EXCLUDED from
        # outputs_match; its greedy-stream drift vs the bf16/f32 paged leg
        # is measured and reported as quant_quality instead.  With
        # --host-swap-gb it also carries the swap tier — the full capacity
        # stack the CI stress leg exercises.
        quant_leg = "paged_quant"
        per_layout[quant_leg] = _serve_one_layout(
            args, cfg, params, rt, "paged",
            prefix_caching=not args.no_prefix_cache, speculate=spec,
            kv_dtype=args.kv_dtype, host_swap_bytes=swap_bytes)
        layouts = layouts + [quant_leg]

    outputs = {lo: per_layout[lo].pop("_outputs") for lo in layouts}
    metrics = {
        "arch": args.arch,
        "requests": args.requests,
        "slots": args.slots,
        "prompt_len": args.prompt_len,
        "prompt_len_max": args.prompt_len_max,
        "new_tokens": args.new_tokens,
        "decode_chunk": args.decode_chunk,
        "page_size": args.page_size,
        "num_pages": args.num_pages,
    }
    # primary layout's fields stay top-level (BENCH trajectory continuity)
    primary = per_layout[layouts[0]]
    metrics.update({k: v for k, v in primary.items()
                    if k not in ("cache_layout",)})
    metrics["cache_layout"] = args.cache_layout
    metrics["shared_prefix_len"] = args.shared_prefix_len
    metrics["kv_dtype"] = getattr(args, "kv_dtype", None)
    metrics["pool_mb"] = getattr(args, "pool_mb", None)
    metrics["host_swap_gb"] = getattr(args, "host_swap_gb", 0) or 0
    metrics["layouts"] = per_layout
    match_legs = [lo for lo in layouts if lo != quant_leg]
    if len(match_legs) >= 2:
        metrics["outputs_match"] = all(
            outputs[lo] == outputs[match_legs[0]]
            for lo in match_legs[1:])
    if quant_leg is not None:
        # greedy-stream drift of the quantized leg vs the exact paged leg:
        # positionwise token match rate + how many whole streams survived
        ref, q = outputs["paged"], outputs[quant_leg]
        tot = hit = exact = 0
        for a, b in zip(ref, q):
            tot += max(len(a), len(b))
            hit += sum(1 for x, y in zip(a, b) if x == y)
            exact += int(a == b)
        metrics["quant_quality"] = {
            "kv_dtype": args.kv_dtype,
            "vs_layout": "paged",
            "token_match_rate": round(hit / max(1, tot), 4),
            "exact_streams": exact,
            "streams": len(ref),
        }
    if "dense" in per_layout and "paged" in per_layout:
        d, p = per_layout["dense"], per_layout["paged"]
        metrics["paged_vs_dense_tok_per_s"] = round(
            p["tok_per_s"] / max(d["tok_per_s"], 1e-9), 3)
    if spec is not None:
        base_lo = "paged" if "paged" in per_layout else layouts[0]
        metrics["duplicates"] = getattr(args, "duplicates", 0) or 0
        metrics["speculation"] = dict(
            per_layout[base_lo]["speculation"],
            spec_vs_base_tok_per_s=round(
                per_layout[base_lo]["tok_per_s"] /
                max(per_layout[base_lo + "_nospec"]["tok_per_s"], 1e-9),
                3))
    if mesh is not None:
        metrics["mesh"] = {"tp": int(mesh.shape["model"]),
                           "axes": list(mesh.axis_names)}
        if "paged" in per_layout:
            metrics["sharded_vs_paged_tok_per_s"] = round(
                per_layout["paged_sharded"]["tok_per_s"] /
                max(per_layout["paged"]["tok_per_s"], 1e-9), 3)
    return metrics


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-9b-smoke")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--prompt-len-max", type=int, default=None,
                    help="mixed-length trace: prompts uniform in "
                         "[prompt-len, prompt-len-max]")
    ap.add_argument("--new-tokens", type=int, default=12)
    ap.add_argument("--repeats", type=int, default=3,
                    help="serve the trace N times per layout and report "
                         "the median run (short traces are noisy)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--decode-chunk", type=int, default=16,
                    help="tokens decoded per fused device dispatch")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="split prompts into chunks of this many tokens "
                         "inside the prefill dispatch (bounds activations)")
    ap.add_argument("--cache-layout", default="dense",
                    choices=("dense", "paged", "both"),
                    help="KV-cache layout; 'both' A/Bs the two and "
                         "cross-checks greedy outputs")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per page (paged layout)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="full-class pool size in pages (paged layout); "
                         "default = dense-equivalent slots*max_len/page")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="trace mode: every prompt starts with the same "
                         "N-token prefix (system-prompt traffic); reports "
                         "prefix hit rate and prefill-dispatch savings, "
                         "and cross-checks greedy outputs against the "
                         "prefix-cache-disabled paged engine")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable automatic prefix caching on the paged "
                         "layout")
    ap.add_argument("--speculate", type=int, default=None, metavar="K",
                    help="speculative decoding (greedy-only): draft K "
                         "tokens per slot via the n-gram proposer and "
                         "verify the whole chain in one fused dispatch; "
                         "adds a '<layout>_nospec' leg on the identical "
                         "trace for the speedup ratio and extends "
                         "outputs_match to spec vs non-spec")
    ap.add_argument("--no-speculate", action="store_true",
                    help="force speculation off (overrides --speculate)")
    ap.add_argument("--duplicates", type=int, default=0, metavar="N",
                    help="trace mode: append N duplicate requests "
                         "(cycling over the originals) — the "
                         "popular-query traffic where cross-request "
                         "drafting gets real acceptance")
    ap.add_argument("--kv-dtype", default=None,
                    choices=("fp8_e4m3", "int8"),
                    help="store paged K/V quantized (per-token fp16 "
                         "scales in a parallel pool; kernels apply them "
                         "in-register): adds a 'paged_quant' leg excluded "
                         "from outputs_match, with greedy-stream drift vs "
                         "the exact paged leg under 'quant_quality'")
    ap.add_argument("--pool-mb", type=float, default=None,
                    help="full-class pool budget in MiB (paged layout, "
                         "overrides --num-pages): quantized legs get "
                         "proportionally more pages from the same bytes")
    ap.add_argument("--host-swap-gb", type=float, default=0,
                    help="host-RAM swap tier budget in GiB: evicted "
                         "prefix pages demote to host instead of "
                         "dropping, and a later hit promotes them back "
                         "(DMA instead of recompute); adds a lossless "
                         "'paged_swap' leg to outputs_match")
    ap.add_argument("--mesh", default=None,
                    help="shard the paged pool across devices: tp=N "
                         "partitions every page array's kv-head / "
                         "latent-rank axis over an N-device mesh and "
                         "serves the trace once more as the "
                         "'paged_sharded' layout (cross-checked via "
                         "outputs_match; per-device bytes under "
                         "memory.sharding)")
    ap.add_argument("--async", dest="run_async", action="store_true",
                    help="open-loop async serving bench: seeded Poisson "
                         "arrivals at --arrival-rate, per-request token "
                         "streams with per-token timestamps, chunked "
                         "prefill interleaved with decode; reports tail "
                         "TTFT/ITL and asserts greedy streams are "
                         "bit-identical to the sync engine on the same "
                         "trace (writes BENCH_serving_async.json unless "
                         "--json overrides)")
    ap.add_argument("--arrival-rate", type=float, default=4.0,
                    help="offered load in requests/s for --async "
                         "(open-loop Poisson, seeded by --seed)")
    ap.add_argument("--prefill-quantum", type=int, default=None,
                    help="tokens per interleaved prefill slice on the "
                         "async engine (default: --prefill-chunk or 32); "
                         "bounds how long one admission can stall "
                         "in-flight streams' ITL")
    ap.add_argument("--long-prompt-len", type=int, default=0,
                    help="async trace mode: every --long-every-th "
                         "request gets a prompt this long — the "
                         "interleave stress case")
    ap.add_argument("--long-every", type=int, default=3,
                    help="period of long prompts in the async trace")
    ap.add_argument("--long-new-tokens", type=int, default=None,
                    help="decode budget for the long-prompt requests "
                         "(default: --new-tokens); small values make "
                         "them prefill-dominated batch jobs")
    ap.add_argument("--dp", type=int, default=1,
                    help="async: serve a second leg through N "
                         "data-parallel engine replicas behind the "
                         "prefix-affinity router (tp per replica from "
                         "--mesh)")
    ap.add_argument("--dp-arrival-rate", type=float, default=None,
                    help="offered load for the --dp leg (default: "
                         "--arrival-rate)")
    ap.add_argument("--json", default="BENCH_serving.json",
                    help="write metrics here ('' to disable)")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip the deploy-time engine warmup (cold-start "
                         "costs then land in the timed trace)")
    args = ap.parse_args(argv)

    enable_compilation_cache()
    if args.run_async:
        if args.json == "BENCH_serving.json":
            args.json = "BENCH_serving_async.json"
        metrics = serve_async_bench(args)
        a, s = metrics["async"], metrics["sync_open_loop"]
        print(f"async open-loop @ {metrics['arrival_rate']} req/s: "
              f"{a['served']}/{a['requests']} served, "
              f"{a['tok_per_s']:.1f} tok/s, TTFT p95 "
              f"{a['ttft_s']['p95']}s, ITL p95 {a['itl_s']['p95']}s "
              f"(sync open-loop ITL p95 {s['itl_s']['p95']}s → "
              f"sync/async = {metrics['itl_p95_sync_over_async']})")
        print(f"  greedy streams match sync engine: "
              f"{metrics['outputs_match']}")
        dp = metrics.get("dp")
        if dp:
            print(f"  dp={dp['dp']} routed: tokens_reused "
                  f"{dp['tokens_reused']} (per replica "
                  f"{[p['tokens_reused'] for p in dp['per_replica']]}), "
                  f"routing {dp['routing']['prefix_routed']} by prefix / "
                  f"{dp['routing']['load_routed']} by load")
        if args.json:
            with open(args.json, "w") as fh:
                json.dump(metrics, fh, indent=1)
        return metrics
    metrics = serve_bench(args)
    print(f"served {metrics['requests']} requests "
          f"({metrics['tokens_decoded']} new tokens) in "
          f"{metrics['wall_s']:.2f}s → {metrics['tok_per_s']:.1f} tok/s "
          f"({metrics['slots']} slots, layout={metrics['cache_layout']}, "
          f"{metrics['dispatches']['decode']} decode dispatches, "
          f"{metrics['dispatches']['prefill']} prefill dispatches, "
          f"TTFT p50 {metrics['ttft_s']['p50']}s)")
    for lo, m in metrics.get("layouts", {}).items():
        mem = m["memory"]
        print(f"  {lo}: {m['tok_per_s']:.1f} tok/s, peak resident "
              f"{mem['peak_resident_cache_bytes']} B "
              f"({mem['bytes_per_live_token']} B/live-token), "
              f"physical {mem['physical_cache_bytes']} B, "
              f"preemptions {m['preemptions']}")
        ht = mem.get("host_tier")
        if ht and ht.get("enabled"):
            print(f"    host swap tier: {ht['demotions']} demotions, "
                  f"{ht['promotions']} promotions (hit rate "
                  f"{ht['promote_hit_rate']:.2f}), {ht['host_drops']} "
                  f"drops, {ht['demoted_pages']} pages "
                  f"({ht['demoted_bytes']} B) resident on host")
        sh = mem.get("sharding")
        if sh:
            pd = sh["per_device"]
            print(f"    pool sharded tp={sh['tp']} over '{sh['axis']}': "
                  f"per-device peak resident "
                  f"{pd['peak_resident_cache_bytes']} B, physical "
                  f"{pd['physical_cache_bytes']} B")
        pf = m.get("prefix", {})
        if pf.get("tokens_reused"):
            print(f"    prefix cache: {pf['hits']} hits "
                  f"(rate {pf['hit_rate']}), {pf['tokens_reused']} tokens "
                  f"reused, {pf['cow_copies']} COW copies, prefill "
                  f"dispatch savings {pf['prefill_savings']:.1%} "
                  f"({pf['tokens_prefilled']}/{pf['prompt_tokens']} "
                  f"prompt tokens prefilled)")
    if "outputs_match" in metrics:
        ratio = metrics.get("paged_vs_dense_tok_per_s")
        print(f"  greedy outputs match across layouts: "
              f"{metrics['outputs_match']}"
              + (f" (paged/dense tok/s = {ratio})" if ratio is not None
                 else ""))
    qq = metrics.get("quant_quality")
    if qq:
        print(f"  quantized leg ({qq['kv_dtype']}): token match rate "
              f"{qq['token_match_rate']} vs {qq['vs_layout']}, "
              f"{qq['exact_streams']}/{qq['streams']} streams exact")
    sp = metrics.get("speculation")
    if sp:
        print(f"  speculation k={sp['k']}: accept rate "
              f"{sp['accept_rate']} ({sp['accepted']}/{sp['proposed']} "
              f"drafts), {sp['accepted_per_dispatch']} committed "
              f"tokens/dispatch, spec/base tok/s = "
              f"{sp['spec_vs_base_tok_per_s']}")
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(metrics, fh, indent=1)
    return metrics


if __name__ == "__main__":
    main()
