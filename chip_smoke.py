#!/usr/bin/env python3
"""Chip smoke test: serve stablelm-1.6b at full width on TPU through the
Pallas kernels, via the engine a user would call.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the multi-chip paths only

One chip: build stablelm-1.6b (24 layers, d_model 2048, 32 heads, bf16,
random weights from ``--seed``) with ``attn_impl="pallas"``, warm up a
paged-layout ``ServeEngine`` with prefix caching on, serve 8 requests
that share a 128-token system prompt, and check that every request
completed with in-vocabulary tokens and finite logits, that the compiled
decode step holds the Pallas kernels (``tpu_custom_call``), and that the
paged decode kernel agrees with the float32 reference at the served
shapes.

``--four-chips``: the same model with the paged pool sharded ``tp=4``,
compared token for token with a one-chip engine on the same trace, then
``dp=4`` one-chip replicas behind the prefix-affinity router, each
replica's params and pool asserted on its own device.

Earlier lines report compile seconds, tokens served and memory; they are
smoke-run figures, not benchmark measurements.  The last line is one JSON
object, ``{"ok": true, "device": {...}}``.  Without a TPU, or outside a
checkout of this repository, the script exits non-zero and prints no
result.  Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

MODEL = "stablelm-1.6b"
SLOTS = 8
MAX_LEN = 1024
PAGE_SIZE = 16
DECODE_CHUNK = 16
NEW_TOKENS = 64
SHARED_PREFIX = 128
# paged decode kernel vs the float32 reference: bf16 K/V and queries,
# fp32 accumulation, bf16 output (8 mantissa bits → ~4e-3 relative)
KERNEL_ATOL = KERNEL_RTOL = 2e-2
#: Pallas interpret mode — never on the chip; a CPU rehearsal of this
#: script's phases sets it
INTERPRET = False


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def build_model(seed: int):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.model import transformer as tf
    from repro.model.layers import Runtime

    cfg = get_config(MODEL)
    rt = Runtime(attn_impl="pallas", interpret=INTERPRET,
                 param_dtype=jnp.bfloat16, activation_dtype=jnp.bfloat16)
    t0 = time.perf_counter()
    params = jax.jit(lambda k: tf.init(cfg, k, rt)[0])(
        jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    n = tf.param_count(params)
    log(f"model {MODEL}: {n / 1e9:.3f}B params in bf16 "
        f"({n * 2 / 2**30:.2f} GiB), built in "
        f"{time.perf_counter() - t0:.1f}s")
    return cfg, rt, params


def make_trace(cfg, seed: int, n: int = 8) -> list:
    """``n`` prompts: a shared 128-token system prompt plus a distinct
    160-256-token tail (288-384 tokens in all), so prefix caching serves
    the head of every prompt after the first."""
    import numpy as np

    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab, size=(SHARED_PREFIX,))
    tails = rng.integers(160, 257, size=(n,))
    return [np.concatenate([shared, rng.integers(0, cfg.vocab, size=(t,))])
            .astype(np.int32) for t in tails]


def make_engine(cfg, params, rt, mesh=None, engine_cls=None, **kw):
    import jax.numpy as jnp

    from repro.serving.engine import ServeEngine

    cls = engine_cls or ServeEngine
    return cls(cfg, params, slots=SLOTS, max_len=MAX_LEN, rt=rt,
               dtype=jnp.bfloat16, decode_chunk=DECODE_CHUNK,
               cache_layout="paged", page_size=PAGE_SIZE,
               prefix_caching=True, mesh=mesh, **kw)


def serve(engine, prompts, new_tokens: int) -> list:
    from repro.serving.engine import Request

    reqs = [Request(rid=i, prompt=p, max_new_tokens=new_tokens)
            for i, p in enumerate(prompts)]
    for r in reqs:
        engine.submit(r)
    engine.run()
    return reqs


def check_outputs(cfg, engine, reqs, new_tokens: int, label: str) -> None:
    import numpy as np

    for r in reqs:
        if not r.done or len(r.generated) != new_tokens:
            fail(f"{label}: request {r.rid} incomplete "
                 f"({len(r.generated)}/{new_tokens} tokens)")
        toks = np.asarray(r.generated)
        if toks.min() < 0 or toks.max() >= cfg.vocab:
            fail(f"{label}: request {r.rid} emitted out-of-vocab tokens")
    logits = np.asarray(engine._last_logits)
    if not np.isfinite(logits).all():
        fail(f"{label}: non-finite logits")


def compiled_decode_step(engine):
    """The engine's fused decode loop compiled at its live state."""
    fn = engine._get_loop(engine.decode_chunk)
    args = (engine.params, engine.caches, engine._kv_len,
            engine._last_logits, engine._remaining, engine.key,
            engine.kv.tables())
    return fn.lower(*args).compile()


def check_paged_kernel(cfg, engine, seed: int) -> float:
    """The paged decode kernel at the served shapes (slots, the engine's
    pool and block-table width) against ``impl="ref"`` in float32.
    Returns the largest absolute error."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    pool = engine.caches[0][0]["attn"]["k_pages"]    # [L, P, Hkv, ps, dh]
    _, n_pages, hkv, ps, dh = pool.shape
    width = engine.kv.classes["full"].table_width
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 4)
    q = jax.random.normal(ks[0], (SLOTS, cfg.n_heads, 1, dh), jnp.bfloat16)
    k = jax.random.normal(ks[1], (n_pages, hkv, ps, dh), jnp.bfloat16)
    v = jax.random.normal(ks[2], (n_pages, hkv, ps, dh), jnp.bfloat16)
    bt = jax.random.permutation(ks[3], n_pages)[:SLOTS * width]
    bt = bt.reshape(SLOTS, width).astype(jnp.int32)
    kv_len = jnp.asarray(np.linspace(1, width * ps, SLOTS).astype(np.int32))
    got = ops.fusemax_decode_paged(q, k, v, bt, kv_len, impl="pallas",
                                   interpret=INTERPRET)
    # a float32 matmul on TPU runs in bf16 passes unless told otherwise
    with jax.default_matmul_precision("highest"):
        want = ops.fusemax_decode_paged(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), bt, kv_len, impl="ref")
    got = np.asarray(got.astype(jnp.float32))
    want = np.asarray(want)
    err = np.abs(got - want)
    if not np.isfinite(got).all() or \
            (err > KERNEL_ATOL + KERNEL_RTOL * np.abs(want)).any():
        fail(f"paged decode kernel vs f32 reference: max abs error "
             f"{err.max():.3g} beyond atol={KERNEL_ATOL} "
             f"rtol={KERNEL_RTOL}")
    return float(err.max())


def open_cache() -> None:
    from repro.serving.engine import enable_compilation_cache

    path = enable_compilation_cache()
    n = len(os.listdir(path)) if os.path.isdir(path) else 0
    log(f"compile cache: {path} ({n} entries at start)")


def one_chip(seed: int) -> None:
    import jax

    open_cache()
    cfg, rt, params = build_model(seed)
    engine = make_engine(cfg, params, rt)
    prompts = make_trace(cfg, seed)
    warm = engine.warmup(sorted({len(p) for p in prompts}))
    log(f"warmup (compiles or cache loads): {warm:.1f}s")

    t0 = time.perf_counter()
    reqs = serve(engine, prompts, NEW_TOKENS)
    dt = time.perf_counter() - t0
    check_outputs(cfg, engine, reqs, NEW_TOKENS, "one-chip serve")
    st = engine.stats
    if st["tokens_reused"] < (len(prompts) - 1) * SHARED_PREFIX:
        fail(f"prefix cache reused {st['tokens_reused']} tokens, expected "
             f">= {(len(prompts) - 1) * SHARED_PREFIX}")
    n_tok = sum(len(r.generated) for r in reqs)
    log(f"served {len(reqs)} requests, {n_tok} new tokens in {dt:.2f}s "
        f"({n_tok / dt:.1f} tok/s incl. any compiles; smoke figure, not a "
        f"measurement); prefix hits {st['prefix_hits']}, tokens reused "
        f"{st['tokens_reused']}, prefill dispatches "
        f"{st['prefill_dispatches']}, decode dispatches "
        f"{st['decode_dispatches']}")

    compiled = compiled_decode_step(engine)
    if "tpu_custom_call" not in compiled.as_text():
        fail("compiled decode step holds no tpu_custom_call (Pallas "
             "kernel missing from the served path)")
    mem = compiled.memory_analysis()
    log(f"compiled decode step holds tpu_custom_call; its memory_analysis: "
        f"arguments {mem.argument_size_in_bytes / 2**30:.2f} GiB, "
        f"temporaries {mem.temp_size_in_bytes / 2**30:.2f} GiB")

    err = check_paged_kernel(cfg, engine, seed)
    log(f"paged decode kernel vs f32 reference at served shapes: max abs "
        f"error {err:.3g} (atol={KERNEL_ATOL}, rtol={KERNEL_RTOL})")
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"peak_bytes_in_use {stats['peak_bytes_in_use']} "
            f"({stats['peak_bytes_in_use'] / 2**30:.2f} GiB)")


def _leaf_devices(tree) -> set:
    import jax

    out = set()
    for leaf in jax.tree.leaves(tree):
        out |= set(leaf.devices())
    return out


def four_chips(seed: int) -> None:
    import gc

    import jax

    from repro.launch.mesh import make_mesh, make_replica_meshes
    from repro.serving.scheduler import (
        AsyncRequest, AsyncServeEngine, DataParallelAsyncEngine,
    )

    if len(jax.devices()) < 4:
        fail(f"--four-chips needs 4 devices, JAX found "
             f"{len(jax.devices())}")
    open_cache()
    cfg, rt, params = build_model(seed)
    prompts = make_trace(cfg, seed)
    new_tokens = 32

    # reference: the same trace on a one-chip engine
    t0 = time.perf_counter()
    ref_eng = make_engine(cfg, params, rt)
    ref = serve(ref_eng, prompts, new_tokens)
    check_outputs(cfg, ref_eng, ref, new_tokens, "one-chip reference")
    ref_out = [list(r.generated) for r in ref]
    log(f"one-chip reference served in {time.perf_counter() - t0:.1f}s")
    del ref_eng
    gc.collect()

    # tp=4: the paged pool sharded over the kv-head axis
    t0 = time.perf_counter()
    mesh = make_mesh((4,), ("model",))
    tp_eng = make_engine(cfg, params, rt, mesh=mesh)
    tp = serve(tp_eng, prompts, new_tokens)
    check_outputs(cfg, tp_eng, tp, new_tokens, "tp=4")
    leaf = tp_eng.caches[0][0]["attn"]["k_pages"]
    shard = leaf.addressable_shards[0].data
    if shard.size * 4 != leaf.size:
        fail(f"tp=4 pool not sharded 4-way: shard {shard.shape} of "
             f"{leaf.shape}")
    tp_out = [list(r.generated) for r in tp]
    same = sum(a == b for a, b in zip(ref_out, tp_out))
    tok_same = sum(x == y for a, b in zip(ref_out, tp_out)
                   for x, y in zip(a, b))
    first = [next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                  None) for a, b in zip(ref_out, tp_out)]
    log(f"tp=4 vs one chip: {same}/{len(ref_out)} streams identical, "
        f"{tok_same}/{len(prompts) * new_tokens} tokens equal, first "
        f"divergence per stream {first}; served in "
        f"{time.perf_counter() - t0:.1f}s, pool shard {shard.shape} of "
        f"{leaf.shape}")
    del tp_eng, leaf, shard
    gc.collect()

    # dp=4: one-chip replicas behind the prefix-affinity router
    t0 = time.perf_counter()
    meshes = make_replica_meshes(4, 1)
    engines = [make_engine(cfg, params, rt, mesh=m,
                           engine_cls=AsyncServeEngine,
                           prefill_quantum=MAX_LEN)
               for m in meshes]
    placed = []
    for i, e in enumerate(engines):
        devs = _leaf_devices(e.params) | _leaf_devices(e.caches)
        if len(devs) != 1:
            fail(f"dp replica {i} spans devices {devs}")
        placed.append(devs.pop())
    if len(set(placed)) != 4:
        fail(f"dp replicas share devices: {placed}")
    dpe = DataParallelAsyncEngine(engines)
    now = dpe.clock.now()
    dreqs = [AsyncRequest(rid=i, prompt=p.copy(),
                          max_new_tokens=new_tokens, arrival=now)
             for i, p in enumerate(prompts)]
    dpe.serve_trace(dreqs)
    for r in dreqs:
        if not r.done or len(r.generated) != new_tokens:
            fail(f"dp=4: request {r.rid} incomplete")
    summ = dpe.stats_summary()
    dp_out = [list(r.generated) for r in dreqs]
    dp_same = sum(a == b for a, b in zip(ref_out, dp_out))
    log(f"dp=4 replicas on devices {[d.id for d in placed]}; routing "
        f"{summ['routing']}; {dp_same}/{len(ref_out)} streams identical "
        f"to the one-chip engine; served in "
        f"{time.perf_counter() - t0:.1f}s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip paths (tp=4 pool, dp=4 "
                         "replicas) on a four-chip host")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for the random weights and the trace")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"needs a TPU; JAX found platform {dev.platform!r}")
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        fail(f"{ROOT} is not a checkout of this repository (no src/repro)")
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
