"""Serving engine: device-resident batched prefill + fused multi-step decode.

``ServeEngine`` manages a fixed-capacity decode batch (continuous
batching): requests occupy slots; each slot has its own ``kv_len``; decode
runs the whole batch through the fused ``transformer.decode_loop`` (the
FuseMax split-K decode kernel handles per-slot ragged lengths in-kernel via
scalar prefetch).  Finished slots are refilled from the queue — the
standard production pattern (vLLM-style).

Two cache layouts, A/B-able via ``cache_layout`` and bit-identical under
greedy decoding:

  * ``"dense"`` — per-slot ``[slots, max_len]`` rows (the classic layout):
    admission needs only a free slot, memory is reserved up front.
  * ``"paged"`` — a page pool with per-slot block tables
    (:mod:`repro.serving.kv_cache`): resident memory tracks live tokens,
    and admission is *pages + slot* — a request enters as soon as a slot
    is free AND its prompt's pages fit the pool.  Slots grow page-by-page
    as they decode; on pool exhaustion the youngest slot is preempted back
    to the queue (recompute-style: its prompt + generated tokens re-prefill
    on re-admission, which reproduces the greedy stream exactly), and
    completed requests return their pages to the free list.  With
    ``prefix_caching`` (default, auto-disabled for windowed/SSM/MoE
    configs) a
    completed request's full pages are instead demoted into a token-hash
    prefix index: later prompts sharing the prefix map those pages into
    their block tables at admission and prefill only the uncached tail —
    ``stats`` reports ``prefix_hits`` / ``tokens_reused`` / ``cow_copies``
    and greedy outputs stay identical with the feature on or off.

The hot path is device-resident end-to-end:

  * **Batched bucketed prefill** — admitted prompts are padded to
    power-of-two length buckets and grouped, then written into their cache
    slots with ONE jit'd call per bucket (dense: fresh mini-cache +
    ``tf.scatter_cache_slots``; paged: straight into the page pool through
    the block tables — no mini-cache materialized).  Jit keys are
    (group width, bucket, shared-prefix offset), so a fresh prompt length
    no longer triggers a fresh compile: padded tails are masked (ring
    writes, page writes, SSM stepping) via ``true_len`` and each row's
    logits are gathered at its real last token.  Long prompts are
    processed in ``prefill_chunk``-sized pieces *inside* the same jit'd
    call (``kv_offset`` continuation); prefix-cache hits prefill only the
    tail beyond their static ``cached_len`` offset.
  * **Fused multi-step decode** — one jit'd ``lax.while_loop`` (with
    on-device early exit once every slot's budget is spent) samples,
    appends to the cache, and advances ``kv_len`` for up to
    ``decode_chunk`` tokens per dispatch; caches and per-slot state are
    donated so no per-step copy survives.
    For the paged layout the engine reserves every slot's worst-case page
    growth for the chunk *before* dispatching, so the block tables are
    loop-invariant on device.
  * Host work per decode dispatch is one small transfer (the [N, slots]
    token block) plus queue/free-list bookkeeping.
  * **Speculative decoding** (``speculate=k``, greedy-only) — a
    model-free n-gram proposer (:mod:`repro.serving.speculate`) drafts k
    tokens per slot; ONE verify dispatch scores all k+1 chain positions
    through the same fused kernels and the accepted prefix commits by
    block-table surgery (scratch draft pages promote into the slot's
    owned set, rejected tails drop their refs — no K/V copies, no
    recompute).  Token streams stay bit-identical to the base loop;
    ``stats`` reports ``spec_dispatches`` / ``spec_proposed`` /
    ``spec_accepted``.

Greedy (temperature=0) token streams are bit-identical between the two
layouts and match the per-token reference path: slots are independent
through every layer, the paged read path sees the very same [*, M, *]
arrays the dense path does (gather through the table), and the fused loop
replays the exact per-step sampling/advance order.

``make_serve_step`` / ``make_prefill_step`` / ``make_decode_loop`` build
the jit-able functions the launcher binds to a mesh (these are what the
dry-run lowers).

Device-sharded pool (``mesh=``): with a multi-device mesh the paged
layout's page arrays shard along the kv-head / latent-rank axis over
``shard_axis`` (default "model") — per-device cache bytes drop to
total/tp while the host-side scheduler (admission, growth, preemption,
COW, prefix index) is untouched, because page ids stay global.  Params
and per-slot state replicate; the paged attention ops run head-parallel
under ``shard_map`` and all-gather head outputs, so greedy token streams
stay bit-identical to the single-device paged engine (the three-way
dense/paged/paged+prefix equality extends to a four-way check).
"""
from __future__ import annotations

import contextlib
import dataclasses
import logging
import os
import pathlib
import time
from typing import Any, Callable, Iterable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed import sharding as shd
from repro.kernels.autotune import next_pow2
from repro.model import transformer as tf
from repro.model.layers import Runtime
from repro.serving.kv_cache import PagedKVCache
from repro.serving.speculate import NGramProposer

#: host phases of a scheduler turn: each is a profiler span
#: ``engine.<phase>`` and a counter ``stats["host_ns_<phase>"]`` (dots
#: as underscores) of the nanoseconds spent in it
HOST_PHASES = ("pump", "admit", "prefill", "decode.prepare",
               "decode.launch", "decode.wait", "decode.harvest")


def enable_compilation_cache() -> str:
    """Keep jax's persistent compilation cache in one fixed place, so a
    second process in the same checkout reuses the compiled programs.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it and
    nothing is changed here.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``, found from this file's path.  Returns the
    directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


@contextlib.contextmanager
def assert_no_recompiles():
    """Assert that no jit tracing or XLA compilation happens inside the
    block — the warmup guarantee: a warmed engine must serve resent
    traffic entirely from already-built executables (zero retraces, zero
    compiles).  Listens to jax's compile logging (``jax.log_compiles``);
    a warm executable-cache hit emits nothing, while any retrace logs a
    "Finished tracing" / "Compiling" record.  Yields the (live) list of
    offending records and raises AssertionError at exit if it is
    non-empty."""
    records: list[str] = []

    class _Capture(logging.Handler):
        def emit(self, rec):
            msg = rec.getMessage()
            if "Finished tracing" in msg or "Compiling " in msg:
                records.append(msg)

    handler = _Capture(level=logging.DEBUG)
    logger = logging.getLogger("jax")
    old_level = logger.level
    with jax.log_compiles():
        logger.addHandler(handler)
        if logger.level > logging.DEBUG:
            logger.setLevel(logging.DEBUG)
        try:
            yield records
        finally:
            logger.removeHandler(handler)
            logger.setLevel(old_level)
    if records:
        raise AssertionError(
            f"{len(records)} jit retrace/compile(s) inside a "
            f"no-recompile region:\n  " + "\n  ".join(records))


def make_serve_step(cfg: ModelConfig, rt: Runtime = Runtime()):
    """serve_step(params, inputs, caches, kv_len) → (logits, caches).

    ``inputs``: [B, 1] tokens (or [B, 1, d] embeddings); ``kv_len``: [B]
    lengths *including* the new token.  One new token per sequence against
    a KV cache of up to seq_len slots — the decode_* dry-run shape.
    """
    def serve_step(params, inputs, caches, kv_len):
        return tf.decode_step(cfg, params, inputs, caches, kv_len, rt)

    return serve_step


def make_prefill_step(cfg: ModelConfig, max_len: int,
                      rt: Runtime = Runtime()):
    def prefill_step(params, inputs, caches):
        return tf.prefill(cfg, params, {"inputs": inputs}, caches, rt)

    return prefill_step


def make_decode_loop(cfg: ModelConfig, n_steps: int,
                     rt: Runtime = Runtime(), temperature: float = 0.0):
    """Fused N-token decode loop (see :func:`transformer.decode_loop`)."""
    def loop(params, caches, kv_len, last_logits, remaining, key):
        return tf.decode_loop(cfg, params, caches, kv_len, last_logits,
                              remaining, key, n_steps=n_steps, rt=rt,
                              temperature=temperature)

    return loop


def sample_logits(logits: jnp.ndarray, key, temperature: float = 0.0):
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature, axis=-1)


def speculation_supported(cfg: ModelConfig) -> bool:
    """True when every layer is global GQA/MLA attention + dense MLP.

    The verify path (:func:`transformer.verify_step`) scores P chain
    positions against the cache in one dispatch; that requires attention
    state addressable by absolute position.  Windowed rings hold only a
    trailing window (a partially-rejected chain would leave phantom ring
    writes), SSM state is a running summary that cannot roll back, and
    MoE expert capacity depends on the evaluated chunk length — a P-token
    verify would route differently than P single-token steps, breaking
    the bit-identity the accept rule relies on.
    """
    return all(s.attn in ("gqa", "mla") and s.window is None
               and s.mlp == "dense" and s.ssm is None
               and not s.parallel_ssm
               for s in cfg.layer_specs())


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # [S] int32
    max_new_tokens: int = 16
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False
    ttft: Optional[float] = None       # seconds, submit → first token known
                                       # (synchronous ``submit`` only)
    preemptions: int = 0               # times bounced back to the queue


class ServeEngine:
    """Continuous-batching engine over a fixed slot count.

    Host-side orchestration (queueing, slot + page management) around two
    jit'd device programs: bucket-batched prefill and the fused multi-step
    decode loop.  ``stats`` counts device dispatches so callers can assert
    the dispatch economics (prefill dispatches independent of prompt
    length; decode dispatches ≈ tokens / decode_chunk), and its
    ``host_ns_*`` counters time each host phase (``HOST_PHASES``), each
    also a profiler span ``engine.<phase>``; ``memory_stats`` reports cache
    residency for the layout A/B.
    """

    def __init__(self, cfg: ModelConfig, params, *, slots: int,
                 max_len: int, rt: Runtime = Runtime(),
                 temperature: float = 0.0, dtype=jnp.float32,
                 decode_chunk: int = 16,
                 prefill_chunk: Optional[int] = None,
                 cache_layout: str = "dense",
                 page_size: int = 16,
                 num_pages: Optional[int] = None,
                 prefix_caching: bool = True,
                 speculate: Optional[int] = None,
                 kv_dtype: Optional[str] = None,
                 pool_bytes: Optional[int] = None,
                 host_swap_bytes: int = 0,
                 mesh=None, shard_axis: str = "model"):
        if cache_layout not in ("dense", "paged"):
            raise ValueError(f"unknown cache_layout: {cache_layout!r}")
        if cache_layout != "paged" and (kv_dtype is not None
                                        or pool_bytes is not None
                                        or host_swap_bytes):
            raise ValueError(
                "kv_dtype / pool_bytes / host_swap_bytes quantize and swap "
                "*pages* — they require cache_layout='paged'")
        shard = None
        if mesh is not None and shard_axis not in mesh.axis_names:
            raise ValueError(
                f"mesh axes {tuple(mesh.axis_names)} have no "
                f"{shard_axis!r} axis to shard the paged pool over — "
                f"pass shard_axis= or build the mesh with a "
                f"{shard_axis!r} axis")
        if mesh is not None and int(mesh.shape[shard_axis]) > 1:
            if cache_layout != "paged":
                raise ValueError(
                    "pool sharding (mesh=) requires cache_layout='paged' — "
                    "the dense layout reserves worst-case rows per slot "
                    "and is not device-sharded")
            shd.validate_kv_shard(cfg, int(mesh.shape[shard_axis]))
            shard = shd.KVShard(mesh=mesh, axis=shard_axis)
            # page pools shard; params and per-step state replicate so
            # every non-paged op stays bit-identical to the 1-device path
            params = jax.device_put(params, NamedSharding(mesh, P()))
            rt = dataclasses.replace(rt, kv_shard=shard)
        placement = contextlib.nullcontext()
        if mesh is not None and mesh.size == 1:
            # a one-device replica (dp serving): params, caches and the
            # per-slot state all live on that device
            device = mesh.devices.flat[0]
            params = jax.device_put(params, device)
            placement = jax.default_device(device)
        self.spec_k = None
        self.proposer = None
        if speculate is not None:
            k = int(speculate)
            if k < 1:
                raise ValueError(f"need speculate >= 1, got {speculate}")
            if temperature > 0.0:
                raise ValueError(
                    "speculative decoding is greedy-only: the accept rule "
                    "commits a draft token iff it equals the model's own "
                    "argmax, which reproduces the non-speculative stream "
                    "only at temperature=0")
            if shard is not None:
                raise ValueError(
                    "speculative decoding does not support the "
                    "device-sharded pool (mesh=) — the verify kernels run "
                    "unsharded; drop mesh= or --speculate")
            if not speculation_supported(cfg):
                raise ValueError(
                    "speculative decoding needs every layer to be global "
                    "GQA/MLA attention with a dense MLP (no sliding "
                    "windows, SSM state, or MoE routing — see "
                    "speculation_supported)")
            self.spec_k = k
            # proposer position 0 guesses the model's *next* token; the
            # verify chain feeds the model's own argmax there, so k drafts
            # need k+1 proposed positions (propose(...)[1:] is the chain)
            self.proposer = NGramProposer(k=k + 1)
        self.cfg = cfg
        self.params = params
        self.rt = rt
        self.slots = slots
        self.max_len = max_len
        self.temperature = temperature
        self.decode_chunk = max(1, decode_chunk)
        self.prefill_chunk = None if prefill_chunk is None \
            else max(1, prefill_chunk)
        self.cache_dtype = dtype
        self.cache_layout = cache_layout
        with placement:
            if cache_layout == "paged":
                self.kv = PagedKVCache(cfg, slots, max_len, dtype,
                                       page_size=page_size,
                                       num_pages=num_pages,
                                       prefix_caching=prefix_caching,
                                       kv_dtype=kv_dtype,
                                       pool_bytes=pool_bytes,
                                       host_swap_bytes=host_swap_bytes,
                                       shard=shard)
                self.caches = self.kv.caches
                # the swap tier snapshots page contents at demotion time;
                # hand it a live view of the engine's current cache pytree
                # (COW and the decode loop rebind self.caches every
                # dispatch)
                self.kv.cache_source = lambda: self.caches
                if shard is not None and any(
                        s.attn == "mla" for s in cfg.layer_specs()):
                    w = self.kv.classes["full"].table_width
                    if w % shard.size:
                        raise ValueError(
                            f"MLA rank-sharded decode sweeps the block "
                            f"table in contiguous per-device page strips, "
                            f"so the table width {w} (= ceil(max_len/"
                            f"page_size)) must divide by tp={shard.size} "
                            f"— adjust max_len or page_size")
            else:
                self.kv = None
                self.caches = tf.init_cache(cfg, slots, max_len, dtype)
            # host mirrors of per-slot state (device copies in _kv_len &c)
            self.kv_len = np.zeros((slots,), np.int32)
            self.remaining = np.zeros((slots,), np.int32)
            self.active: list[Optional[Request]] = [None] * slots
            self.queue: list[Request] = []
            self.key = jax.random.PRNGKey(0)
            self._kv_len = jnp.zeros((slots,), jnp.int32)
            self._remaining = jnp.zeros((slots,), jnp.int32)
            self._last_logits = jnp.zeros((slots, cfg.vocab), jnp.float32)
        self._prefill_fns: dict[tuple, Callable] = {}
        self._loop_fns: dict[int, Callable] = {}
        self._spec_fns: dict[int, Callable] = {}
        self._admit_seq = 0
        self._order = [0] * slots          # admission sequence per slot
        self.stats = {"prefill_dispatches": 0, "decode_dispatches": 0,
                      "decode_steps": 0, "tokens_decoded": 0,
                      "preemptions": 0, "peak_live_tokens": 0,
                      "prefix_hits": 0, "tokens_reused": 0,
                      "cow_copies": 0, "tokens_prefilled": 0,
                      "spec_dispatches": 0, "spec_proposed": 0,
                      "spec_accepted": 0,
                      **{"host_ns_" + p.replace(".", "_"): 0
                         for p in HOST_PHASES}}

    @contextlib.contextmanager
    def _span(self, phase: str, **ids):
        """One host phase (``HOST_PHASES``): the profiler span
        ``engine.<phase>`` with ``ids`` as its stats, on the device trace's
        clock (inactive and nearly free when no profiler runs), and its
        duration added to ``stats["host_ns_<phase>"]``, so a run with no
        profiler still sees where the host spent its time."""
        t0 = time.perf_counter_ns()
        try:
            with jax.profiler.TraceAnnotation(f"engine.{phase}", **ids):
                yield
        finally:
            self.stats["host_ns_" + phase.replace(".", "_")] += \
                time.perf_counter_ns() - t0

    # -- jit caches ---------------------------------------------------------

    def _bucket(self, s: int) -> int:
        """Pad prompt lengths to power-of-two buckets (capped at max_len)
        so prefill jit keys are per-bucket, not per-exact-length."""
        return min(next_pow2(s), self.max_len)

    def _prefill_pieces(self, s: int) -> list[tuple[int, int]]:
        chunk = self.prefill_chunk
        if chunk is None or s <= chunk:
            return [(0, s)]
        pieces, off = [], 0
        while off < s:                       # static unroll
            c = min(chunk, s - off)
            pieces.append((off, c))
            off += c
        return pieces

    def _get_prefill(self, n: int, s: int, off0: int = 0) -> Callable:
        """Jit'd: prefill ``n`` prompt *tails* padded to bucket length
        ``s`` into slot rows (dense) or pages (paged); per-row real
        lengths arrive as the ``true_len`` device argument, so the jit key
        is (n, s, off0) only.  ``off0`` (paged layout) is the static
        shared-prefix offset the group was admitted under: positions
        [0, off0) are already resident in mapped prefix pages, and the
        per-row ``cached_len`` device argument masks their page writes so
        shared head pages are read but never rewritten."""
        fn = self._prefill_fns.get((n, s, off0))
        if fn is not None:
            return fn
        cfg, rt = self.cfg, self.rt
        max_len, dtype = self.max_len, self.cache_dtype
        pieces = self._prefill_pieces(s)
        paged = self.kv is not None

        def select_last(logits, lg, true_len, off, c):
            sel = (true_len - 1 >= off) & (true_len - 1 < off + c)
            return jnp.where(sel[:, None], lg.astype(logits.dtype), logits)

        if paged:
            def prefill_into_slots(params, tokens, caches, tables,
                                   slot_ids, true_len, cached_len,
                                   last_logits):
                logits = jnp.zeros((n, cfg.vocab), jnp.float32)
                for off, c in pieces:
                    lg, caches = tf.prefill(
                        cfg, params, {"inputs": tokens[:, off:off + c]},
                        caches, rt, kv_offset=off0 + off,
                        true_len=true_len, block_tables=tables,
                        slot_ids=slot_ids, cached_len=cached_len)
                    logits = select_last(logits, lg, true_len, off0 + off,
                                         c)
                last_logits = last_logits.at[slot_ids].set(logits)
                return last_logits, caches

            fn = jax.jit(prefill_into_slots,
                         donate_argnums=(2, 7))
        else:
            def prefill_into_slots(params, tokens, caches, slot_ids,
                                   true_len, last_logits):
                mini = tf.init_cache(cfg, n, max_len, dtype)
                logits = jnp.zeros((n, cfg.vocab), jnp.float32)
                for off, c in pieces:
                    lg, mini = tf.prefill(
                        cfg, params, {"inputs": tokens[:, off:off + c]},
                        mini, rt, kv_offset=off, true_len=true_len)
                    logits = select_last(logits, lg, true_len, off, c)
                caches = tf.scatter_cache_slots(cfg, caches, mini, slot_ids)
                last_logits = last_logits.at[slot_ids].set(logits)
                return last_logits, caches

            fn = jax.jit(prefill_into_slots,
                         donate_argnums=(2, 5))
        self._prefill_fns[(n, s, off0)] = fn
        return fn

    def _get_loop(self, n_steps: int) -> Callable:
        fn = self._loop_fns.get(n_steps)
        if fn is not None:
            return fn
        cfg, rt, temperature = self.cfg, self.rt, self.temperature
        if self.kv is not None:
            def loop(params, caches, kv_len, last_logits, remaining, key,
                     tables):
                return tf.decode_loop(
                    cfg, params, caches, kv_len, last_logits, remaining,
                    key, n_steps=n_steps, rt=rt, temperature=temperature,
                    block_tables=tables)
        else:
            loop = make_decode_loop(cfg, n_steps, rt, temperature)
        fn = jax.jit(loop, donate_argnums=(1, 2, 3, 4, 5))
        self._loop_fns[n_steps] = fn
        return fn

    def _get_spec(self, p_total: int) -> Callable:
        """Jit'd fused speculate→verify→accept step for a ``p_total``-
        position chain (see :func:`transformer.speculative_step`); the
        jit key is the chain width only."""
        fn = self._spec_fns.get(p_total)
        if fn is not None:
            return fn
        cfg, rt = self.cfg, self.rt
        if self.kv is not None:
            def spec(params, last_logits, drafts, caches, kv_len,
                     remaining, tables):
                return tf.speculative_step(
                    cfg, params, last_logits, drafts, caches, kv_len,
                    remaining, rt, block_tables=tables)
        else:
            def spec(params, last_logits, drafts, caches, kv_len,
                     remaining):
                return tf.speculative_step(
                    cfg, params, last_logits, drafts, caches, kv_len,
                    remaining, rt)
        fn = jax.jit(spec, donate_argnums=(1, 3, 4, 5))
        self._spec_fns[p_total] = fn
        return fn

    # -- request flow -------------------------------------------------------

    def warmup(self, prompt_len: Union[int, Iterable[int]]) -> float:
        """Deploy-time warmup: trigger (or deserialize from the persistent
        compilation cache) the prefill and decode executables for this
        workload shape by serving throwaway full-slot traces, then reset
        the serving state.  Returns the seconds spent.

        Standard serving practice — run before accepting traffic so
        steady-state tok/s and per-request TTFT don't pay first-use costs.
        ``prompt_len`` may be a single length or an iterable (mixed-length
        traffic): one trace per (admission-width power of two, length
        bucket) covers every prefill jit key those lengths can produce,
        plus the decode loops (1 and ``decode_chunk``).

        With prefix caching enabled, a second phase replays identical
        prompts against a *live* index so the tail-offset prefill keys a
        prefix hit produces — (width, tail bucket, shared-prefix offset),
        including the page-aligned COW resend offsets — compile here
        instead of on the first real hit.
        """
        t0 = time.perf_counter()
        prefix_was = False
        if self.kv is not None:
            # phase 1 must compile the *cold* prefill keys: with the index
            # live, the identical dummy prompts would hit each other and
            # skip the cold (offset-0) traces.
            prefix_was = self.kv.prefix_enabled
            self.kv.prefix_enabled = False
        try:
            lens = (prompt_len,) if isinstance(prompt_len, int) \
                else prompt_len
            buckets = sorted({self._bucket(max(1, min(p, self.max_len - 1)))
                              for p in lens})
            counts = {self.slots} | {
                1 << i for i in range((self.slots - 1).bit_length())}

            def trace(count, plen):
                dummies = [Request(rid=-1 - i,
                                   prompt=np.zeros((plen,), np.int32),
                                   max_new_tokens=self.decode_chunk)
                           for i in range(count)]
                for r in dummies:
                    self.submit(r)
                self.run()

            for b in buckets:
                plen = min(b, self.max_len - 1)
                for count in sorted(counts, reverse=True):
                    trace(count, plen)
            if prefix_was:
                # phase 2 — tail-offset keys: identical zero prompts, two
                # waves per (bucket, width) with the index live.  Wave 1
                # registers the prefix (the widths > 1 also exercise
                # same-batch sharing); wave 2 is a full-coverage resend —
                # the page-aligned / COW hit offsets real resend traffic
                # produces.  Cross-bucket hits (longer zeros over shorter
                # registered prefixes) cover the partial-hit offsets.
                self.kv.prefix_enabled = True
                for b in buckets:
                    plen = min(b, self.max_len - 1)
                    for count in sorted(counts, reverse=True):
                        for _ in range(2):
                            trace(count, plen)
            # slots auto-freed on completion; dummy cache rows/pages are
            # fully overwritten by the next admission.  Reset counters and
            # drop the prefix entries the dummy prompts registered —
            # warmup traffic must not hit (or occupy pages for) the real
            # trace.
            for k in self.stats:
                self.stats[k] = 0
            if self.kv is not None:
                self.kv.clear_prefix()
                self.kv.reset_peaks()
            if self.proposer is not None:
                # drop the dummy streams the warmup traces indexed — real
                # traffic must not draft from (or get fake acceptance on)
                # the all-zero warmup prompts
                self.proposer.clear()
        finally:
            if self.kv is not None:
                self.kv.prefix_enabled = prefix_was
        return time.perf_counter() - t0

    def clear_prefix_cache(self) -> int:
        """Drop every reusable-prefix entry so the pool can drain fully
        (e.g. between unrelated traffic phases).  Returns entries
        dropped."""
        if self.kv is None:
            return 0
        return self.kv.clear_prefix()

    def submit(self, req: Request) -> None:
        if len(req.prompt) >= self.max_len:
            raise ValueError(
                f"prompt length {len(req.prompt)} needs at least one free "
                f"cache slot for decode (max_len={self.max_len})")
        if self.kv is not None:
            self.kv.validate_request(len(req.prompt) + req.max_new_tokens)
        req._t_submit = time.perf_counter()
        self.queue.append(req)

    @staticmethod
    def _resume_tokens(req: Request) -> np.ndarray:
        """Prompt to prefill at (re-)admission: after a preemption the
        generated tokens are replayed as prompt — greedy continuation is
        then exactly the uninterrupted stream (recompute preemption)."""
        if req.generated:
            return np.concatenate(
                [np.asarray(req.prompt, np.int32),
                 np.asarray(req.generated, np.int32)])
        return np.asarray(req.prompt, np.int32)

    def _admit(self) -> None:
        """Fill free slots from the queue.  Dense layout: admission = a
        free slot.  Paged layout: admission = free slot AND the prompt's
        pages (+1 decode token) fit every pool — continuous batching
        backed by actual memory, not worst-case rows.  Prompts are first
        matched against the reusable-prefix index (``kv.admit``): hit
        pages map straight into the slot's block table and only the
        uncached tail is prefilled.  One batched prefill dispatch per
        (shared-prefix length, tail length bucket) group, dispatched
        cold-first so a group that writes fresh prefix pages always runs
        before a group that reads them."""
        admitted: list[tuple[int, Request, np.ndarray, int, list]] = []
        staged_promotes: list = []
        for i in range(self.slots):
            if self.active[i] is not None or not self.queue:
                continue
            req = self.queue[0]
            tokens = self._resume_tokens(req)
            cached, cow_pairs = 0, []
            if self.kv is not None:
                info = self.kv.admit(i, tokens, len(tokens) + 1)
                if info is None:
                    break                # head-of-line waits for pages
                if info["promotes"]:
                    # host→HBM DMA for the matched demoted suffix: start
                    # the transfers NOW so they overlap the rest of the
                    # admission loop (hashing, COW planning, further
                    # admissions); the page scatters land below, before
                    # any COW copy or prefill group can read the pages
                    staged_promotes.extend(
                        self.kv.start_promote(info["promotes"]))
                cached = info["cached_len"]
                cow_pairs = info["cow_pairs"]
                if info["reused"]:
                    self.stats["prefix_hits"] += 1
                    self.stats["tokens_reused"] += info["reused"]
                self.stats["cow_copies"] += len(cow_pairs)
            self.queue.pop(0)
            self.active[i] = req
            self._admit_seq += 1
            self._order[i] = self._admit_seq
            admitted.append((i, req, tokens, cached, cow_pairs))
            if self.proposer is not None:
                # (re-)open the request's draft history with the full
                # resume stream — preemption replay starts clean
                self.proposer.begin(req.rid, tokens)
        if staged_promotes:
            self.caches = self.kv.apply_promote(self.caches,
                                                staged_promotes)
        if not admitted:
            return
        by_group: dict[tuple[int, int], list] = {}
        for slot, req, tokens, cached, cow_pairs in admitted:
            key = (cached, self._bucket(len(tokens) - cached))
            by_group.setdefault(key, []).append(
                (slot, req, tokens, cached, cow_pairs))
        for (off0, sb), group in sorted(by_group.items()):
            # groups dispatch in ascending shared-prefix order: a group
            # that writes fresh prefix pages always runs before one that
            # reads them, and deferred COW copies land here — after their
            # source page's writer, before this group's own prefill
            pairs = [p for g in group for p in g[4]]
            if pairs:
                self.caches = self.kv.apply_cow(self.caches, pairs)
            # pad the group to the next power of two (duplicate rows
            # scatter the same data twice — deterministic): bounded jit
            # keys per bucket without paying full-slot-width prefill FLOPs
            # for a single late admission
            width = next_pow2(len(group))
            padded = group + [group[-1]] * (width - len(group))
            slot_ids = np.array([g[0] for g in padded], np.int32)
            true_len = np.array([len(g[2]) for g in padded], np.int32)
            cached_len = np.array([g[3] for g in padded], np.int32)
            toks = np.zeros((len(padded), sb), np.int32)
            for r, (_, _, t, co, _cp) in enumerate(padded):
                toks[r, :len(t) - co] = t[co:]
            fn = self._get_prefill(len(padded), sb, off0)
            if self.kv is not None:
                self._last_logits, self.caches = fn(
                    self.params, jnp.asarray(toks), self.caches,
                    self.kv.tables(), jnp.asarray(slot_ids),
                    jnp.asarray(true_len), jnp.asarray(cached_len),
                    self._last_logits)
            else:
                self._last_logits, self.caches = fn(
                    self.params, jnp.asarray(toks), self.caches,
                    jnp.asarray(slot_ids), jnp.asarray(true_len),
                    self._last_logits)
            self.stats["prefill_dispatches"] += 1
            for slot, req, tokens, co, _cp in group:
                s = len(tokens)
                self.stats["tokens_prefilled"] += s - co
                self.kv_len[slot] = s
                budget = req.max_new_tokens - len(req.generated)
                # ≥1 token always (the seed engine's semantics), bounded by
                # the request and the cache capacity
                self.remaining[slot] = min(
                    budget, max(1, self.max_len - 1 - s))
        self._sync_live_peak()

    def _preempt(self, slot: int) -> None:
        """Bounce a slot back to the head of the queue, releasing its
        pages (recompute preemption — see :func:`_resume_tokens`)."""
        req = self.active[slot]
        self.kv.release(slot)
        self.active[slot] = None
        self.kv_len[slot] = 0
        self.remaining[slot] = 0
        req.preemptions += 1
        self.stats["preemptions"] += 1
        self.queue.insert(0, req)

    def _preempt_candidates(self) -> list:
        """Slots eligible as preemption victims (the async engine extends
        this with its mid-prefill slots, which hold pages too)."""
        return [j for j, r in enumerate(self.active) if r is not None]

    def _ensure_pages(self, n: int) -> None:
        """Reserve every active slot's worst-case page growth for an
        ``n``-step decode chunk, oldest slot first; on pool exhaustion the
        *youngest* active slot is preempted (so the oldest always makes
        progress — the classic anti-livelock order)."""
        if self.kv is None:
            return
        order = sorted((i for i, r in enumerate(self.active)
                        if r is not None), key=lambda i: self._order[i])
        for i in order:
            while self.active[i] is not None:
                target = int(self.kv_len[i]) + \
                    int(min(n, self.remaining[i]))
                if self.kv.grow(i, target):
                    break
                victim = max(self._preempt_candidates(),
                             key=lambda j: self._order[j])
                self._preempt(victim)

    def _sync_live_peak(self) -> None:
        self.stats["peak_live_tokens"] = max(
            self.stats["peak_live_tokens"], int(self.kv_len.sum()))

    def _decode_chunk(self) -> None:
        """One fused dispatch: up to ``decode_chunk`` tokens for every
        active slot, then harvest + retire finished requests."""
        act = [i for i, r in enumerate(self.active) if r is not None]
        if not act:
            return
        if self.spec_k is not None and \
                all(self.active[i].generated for i in act):
            # speculative path: one verify dispatch commits up to k+1
            # tokens per slot.  Falls through to the base loop when the
            # pool can't back every slot's draft span — _ensure_pages
            # then applies the usual preemption back-pressure.
            if self._spec_step(act):
                return
        if any(not self.active[i].generated for i in act):
            # freshly admitted slot: run a single step first so its first
            # token reaches the host immediately — keeps the reported TTFT
            # a first-token latency, not full-chunk latency
            n = 1
        else:
            # the while_loop exits as soon as every budget is spent, so a
            # full-chunk n costs nothing when fewer steps are needed; two
            # jit keys total {1, decode_chunk} — both built by warmup()
            n = self.decode_chunk
        with self._span("decode.prepare"):
            self._ensure_pages(n)      # may preempt → recompute the batch
            act = [i for i, r in enumerate(self.active) if r is not None]
            if not act:
                return
            rem_before = self.remaining.copy()
            self._kv_len = jnp.asarray(self.kv_len)
            self._remaining = jnp.asarray(self.remaining)
            fn = self._get_loop(n)
            args = (self.params, self.caches, self._kv_len,
                    self._last_logits, self._remaining, self.key)
            if self.kv is not None:
                args = args + (self.kv.tables(),)
        with self._span("decode.launch"):      # enqueues; a compile shows
            toks, self.caches, self._kv_len, self._last_logits, \
                self._remaining, self.key, steps = fn(*args)
        with self._span("decode.wait"):        # the host blocked on the chip
            steps = int(steps)
            toks = np.asarray(toks)                   # [n, slots]; one sync
            self.kv_len = np.array(self._kv_len)      # writable host mirrors
            self.remaining = np.array(self._remaining)
        with self._span("decode.harvest"):
            self.stats["decode_dispatches"] += 1
            self.stats["decode_steps"] += steps
            now = time.perf_counter()
            self._sync_live_peak()
            for i in act:
                req = self.active[i]
                take = int(min(n, rem_before[i]))
                if take > 0:
                    if not req.generated and req.ttft is None and \
                            hasattr(req, "_t_submit"):  # sync submit only
                        req.ttft = now - req._t_submit
                    got = [int(t) for t in toks[:take, i]]
                    req.generated.extend(got)
                    self.stats["tokens_decoded"] += take
                    if self.proposer is not None:
                        self.proposer.extend(req.rid, got)
                if self.remaining[i] <= 0:
                    req.done = True
                    self.active[i] = None
                    self.kv_len[i] = 0
                    if self.proposer is not None:
                        self.proposer.finish(req.rid)
                    if self.kv is not None:
                        # completion path: hand the slot's full token
                        # stream to release so its full pages are demoted
                        # into the reusable-prefix index instead of freed
                        self.kv.release(i, tokens=self._resume_tokens(req))

    def _spec_step(self, act: list) -> bool:
        """One fused speculate→verify→accept dispatch: score a k+1-token
        chain (the model's own next token + the proposer's k drafts) per
        active slot and commit the accepted prefix.

        Draft K/V lands in scratch tail pages reserved up front
        (:meth:`PagedKVCache.reserve_draft`); accept is block-table
        surgery — ``commit_draft`` promotes the scratch pages covering the
        committed length into the slot's owned set and the rejected tail
        rolls back by dropping refs, with no K/V copies or recompute.
        Greedy streams stay bit-identical to the base loop because every
        committed token is the model's own argmax (see
        :func:`transformer.speculative_step`).  Returns False — nothing
        dispatched, nothing left staged — when the pool cannot back every
        active slot's draft span even after prefix eviction.
        """
        k = self.spec_k
        p_total = k + 1
        drafts = np.zeros((self.slots, k), np.int32)
        proposed = np.zeros((self.slots,), np.int64)
        for i in act:
            # position 0 of the proposal guesses the model's next token —
            # the verify chain feeds the model's own argmax there, so the
            # speculative chain is the tail.  Zero-padding unproposed
            # positions is safe: a wrong draft just fails the accept rule.
            d = self.proposer.propose(self.active[i].rid)[1:]
            n = min(len(d), k)
            drafts[i, :n] = d[:n]
            proposed[i] = n
        if self.kv is not None:
            staged, pairs, short = [], [], False
            for i in act:
                span = int(min(p_total, self.remaining[i]))
                res = self.kv.reserve_draft(
                    i, int(self.kv_len[i]), int(self.kv_len[i]) + span)
                if res is None:
                    short = True
                    break
                staged.append(i)
                pairs.extend(res)
            if pairs:
                # COW pairs stand on their own (the slot's ref already
                # moved to the copy), so they must apply even when a later
                # slot's reservation fails and the dispatch is abandoned
                self.caches = self.kv.apply_cow(self.caches, pairs)
                self.stats["cow_copies"] += len(pairs)
            if short:
                for i in staged:
                    self.kv.drop_draft(i)
                return False
        self._kv_len = jnp.asarray(self.kv_len)
        self._remaining = jnp.asarray(self.remaining)
        fn = self._get_spec(p_total)
        args = (self.params, self._last_logits, jnp.asarray(drafts),
                self.caches, self._kv_len, self._remaining)
        if self.kv is not None:
            args = args + (self.kv.tables(),)
        toks, advance, self._kv_len, self._remaining, self._last_logits, \
            self.caches = fn(*args)
        self.stats["decode_dispatches"] += 1
        self.stats["spec_dispatches"] += 1
        self.stats["decode_steps"] += 1    # one model evaluation

        toks = np.asarray(toks)                   # [P, slots]; one sync
        advance = np.asarray(advance)
        self.kv_len = np.array(self._kv_len)      # writable host mirrors
        self.remaining = np.array(self._remaining)
        self._sync_live_peak()
        for i in act:
            req = self.active[i]
            adv = int(advance[i])
            self.stats["spec_proposed"] += int(proposed[i])
            self.stats["spec_accepted"] += max(0, adv - 1)
            if self.kv is not None:
                self.kv.commit_draft(i, int(self.kv_len[i]))
            if adv > 0:
                got = [int(t) for t in toks[:adv, i]]
                req.generated.extend(got)
                self.stats["tokens_decoded"] += adv
                self.proposer.extend(req.rid, got)
            if self.remaining[i] <= 0:
                req.done = True
                self.active[i] = None
                self.kv_len[i] = 0
                self.proposer.finish(req.rid)
                if self.kv is not None:
                    self.kv.release(i, tokens=self._resume_tokens(req))
        return True

    def step(self) -> None:
        """Admit waiting requests, then run one fused decode dispatch."""
        self._admit()
        self._decode_chunk()

    def run(self, max_steps: int = 1000) -> None:
        steps = 0
        while (self.queue or any(r is not None for r in self.active)) \
                and steps < max_steps:
            self.step()
            steps += 1

    # -- accounting ---------------------------------------------------------

    def memory_stats(self) -> dict:
        """Cache-memory accounting for the layout A/B (see
        ``benchmarks/serving_bench.py``).  ``resident_cache_bytes`` is what
        actually holds live tokens: the whole allocation for the dense
        layout, pages-in-use for the paged one."""
        peak_live = max(1, self.stats["peak_live_tokens"])
        if self.kv is not None:
            m = self.kv.memory_stats()
            m["layout"] = "paged"
            m["bytes_per_live_token"] = round(
                m["peak_resident_cache_bytes"] / peak_live, 1)
            m["prefix_cache"].update(
                hits=self.stats["prefix_hits"],
                tokens_reused=self.stats["tokens_reused"],
                cow_copies=self.stats["cow_copies"])
            return m
        # mirror the paged accounting: attention caches vs O(slots) SSM
        # state, so the layout A/B compares like with like
        attn = ssm = 0
        for run in self.caches:
            for layer in run:
                attn += sum(x.nbytes
                            for x in jax.tree.leaves(layer.get("attn", {})))
                ssm += sum(x.nbytes
                           for x in jax.tree.leaves(layer.get("ssm", {})))
        return {
            "layout": "dense",
            "resident_cache_bytes": attn,
            "peak_resident_cache_bytes": attn,
            "physical_cache_bytes": attn,
            "ssm_state_bytes": ssm,
            "bytes_per_live_token": round(attn / peak_live, 1),
        }
