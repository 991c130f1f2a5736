"""Structural lint: declared cascades vs. actual kernel implementations.

The symbolic analysis (:mod:`repro.analysis.passes`) proves what a
*declared* cascade costs; this module proves the *shipped code* actually
implements that cascade:

Pallas kernels
    ``capture_pallas_calls`` monkeypatches ``pl.pallas_call`` with a
    recorder that grabs the grid, every BlockSpec (block shape +
    ``index_map``), the scratch (accumulator) shapes, and the concrete
    scalar-prefetch operands (kv lengths, block tables), then returns
    zeros so the wrapper completes without compiling anything.  The lint
    then *evaluates the real index_maps* over the integer grid: for a
    declared-1-pass kernel every live K/V (or latent) tile must be
    visited exactly once per output fiber with full coverage of the
    logical sequence, the Q/output tiles must be stationary across the
    sequence sweep, and the scratch accumulators must match the declared
    running-state signature (RM/RD/RNV triples for split-K, the ``[G, r]``
    latent accumulator for paged MLA) and must not change when the
    sequence length does.

jnp fallback paths
    ``trace_m_passes`` traces the function to a jaxpr with shaped
    abstract values and ports the avail/ready pass propagation of
    :mod:`repro.core.passes` onto the equations: tensors carrying the
    (distinctively-sized) sequence axis are tracked through reshapes,
    scans (one iterative pass), slices and contractions, and the maximum
    traversal generation is the pass count; a tensor traversed in two
    generations is an O(S) live fiber.

A declared-1-pass kernel that re-reads K/V pages, or an accumulator that
scales with S, raises :class:`LintError`; ``python -m
repro.analysis.report --check`` turns that into a non-zero exit in CI.
"""
from __future__ import annotations

import contextlib
import functools
import itertools
from collections import Counter
from dataclasses import dataclass, field
from typing import Callable, Iterable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.extend.core import Literal

from repro.analysis.cascade import O1, OS, CascadeEntry, REGISTRY


class LintError(AssertionError):
    """A kernel's structure contradicts its declared cascade."""


# ---------------------------------------------------------------------------
# Pallas capture
# ---------------------------------------------------------------------------

@dataclass
class PallasRecord:
    """One intercepted ``pl.pallas_call``: geometry + concrete operands."""

    name: str
    grid: tuple
    in_specs: list
    out_specs: list
    scratch_shapes: list
    num_scalar_prefetch: int
    out_shape: list
    operands: list = field(default_factory=list)

    @property
    def scalar_args(self) -> list:
        """Concrete scalar-prefetch operands (np arrays) for index_maps."""
        return [np.asarray(o) for o in
                self.operands[: self.num_scalar_prefetch]]

    def scratch_sig(self) -> tuple:
        return tuple(
            (tuple(s.shape), jnp.dtype(s.dtype).name)
            for s in self.scratch_shapes
        )


def _kernel_name(kernel) -> str:
    fn = kernel.func if isinstance(kernel, functools.partial) else kernel
    return getattr(fn, "__name__", str(fn))


@contextlib.contextmanager
def capture_pallas_calls():
    """Patch ``pl.pallas_call`` to record geometry and return zeros.

    Works for both call styles in the tree: keyword ``grid=/in_specs=``
    (prefill) and ``grid_spec=PrefetchScalarGridSpec`` (decode).
    """
    records: list[PallasRecord] = []
    orig = pl.pallas_call

    def recorder(kernel, *, out_shape, grid=None, grid_spec=None,
                 in_specs=None, out_specs=None, scratch_shapes=None, **kw):
        if grid_spec is not None:
            g = tuple(grid_spec.grid)
            nsp = int(getattr(grid_spec, "num_scalar_prefetch", 0) or 0)
            ins = list(grid_spec.in_specs)
            outs = grid_spec.out_specs
            scr = list(grid_spec.scratch_shapes or ())
        else:
            g = tuple(grid)
            nsp = 0
            ins = list(in_specs or ())
            outs = out_specs
            scr = list(scratch_shapes or ())
        outs = list(outs) if isinstance(outs, (list, tuple)) else [outs]
        shapes = (list(out_shape) if isinstance(out_shape, (list, tuple))
                  else [out_shape])
        rec = PallasRecord(
            name=_kernel_name(kernel), grid=g, in_specs=ins, out_specs=outs,
            scratch_shapes=scr, num_scalar_prefetch=nsp, out_shape=shapes,
        )

        def fake(*operands):
            rec.operands = list(operands)
            records.append(rec)
            zeros = [jnp.zeros(s.shape, s.dtype) for s in shapes]
            return zeros if isinstance(out_shape, (list, tuple)) else zeros[0]

        return fake

    pl.pallas_call = recorder
    try:
        yield records
    finally:
        pl.pallas_call = orig


# ---------------------------------------------------------------------------
# Grid-sweep checks
# ---------------------------------------------------------------------------

def _eval_index(spec, coords, scalar_args) -> tuple:
    return tuple(int(x) for x in spec.index_map(*coords, *scalar_args))


def tile_visits(
    rec: PallasRecord,
    spec_idx: int,
    fixed: dict,
    live: Optional[Callable[..., bool]] = None,
) -> Counter:
    """Visit counts per distinct tile of operand ``spec_idx``, sweeping
    all grid axes not pinned in ``fixed`` (the output-fiber axes)."""
    sweep = [i for i in range(len(rec.grid)) if i not in fixed]
    spec = rec.in_specs[spec_idx]
    visits: Counter = Counter()
    for combo in itertools.product(*[range(rec.grid[i]) for i in sweep]):
        coords = [0] * len(rec.grid)
        for i, v in fixed.items():
            coords[i] = v
        for i, v in zip(sweep, combo):
            coords[i] = v
        if live is not None and not live(*coords):
            continue
        visits[_eval_index(spec, coords, rec.scalar_args)] += 1
    return visits


def assert_single_sweep(
    rec: PallasRecord,
    spec_idx: int,
    fixed: dict,
    expected_tiles: int,
    live: Optional[Callable[..., bool]] = None,
    what: str = "K",
) -> None:
    """A declared-1-pass kernel must touch every live ``what`` tile
    exactly once per output fiber (no re-reads, no gaps)."""
    visits = tile_visits(rec, spec_idx, fixed, live)
    dup = {t: n for t, n in visits.items() if n > 1}
    if dup:
        raise LintError(
            f"{rec.name}: declared 1-pass but {what} tiles are re-read "
            f"(visit counts {dup} at fiber {fixed}) — a second sweep "
            f"over the sequence")
    if len(visits) != expected_tiles:
        raise LintError(
            f"{rec.name}: {what} sweep covers {len(visits)} tiles at "
            f"fiber {fixed}, expected {expected_tiles}")


def assert_one_layer(
    rec: PallasRecord, spec_idx: int, fixed: dict, layer: int,
    what: str = "K",
) -> None:
    """A kernel over pools stacked on a leading layer axis must fetch
    every tile of the stream from the layer it was handed."""
    got = {t[0] for t in tile_visits(rec, spec_idx, fixed)}
    if got != {layer}:
        raise LintError(
            f"{rec.name}: {what} tiles come from layers {sorted(got)}, "
            f"expected only layer {layer}")


def assert_stationary(
    rec: PallasRecord, spec_idx: int, sweep_axis: int, fixed: dict,
    what: str = "Q",
) -> None:
    """Output-stationarity: the operand's tile must not move while the
    sequence axis sweeps (otherwise the kernel re-reads it per step)."""
    spec = rec.in_specs[spec_idx]
    coords = [0] * len(rec.grid)
    for i, v in fixed.items():
        coords[i] = v
    first = list(coords)
    last = list(coords)
    first[sweep_axis] = 0
    last[sweep_axis] = rec.grid[sweep_axis] - 1
    a = _eval_index(spec, first, rec.scalar_args)
    b = _eval_index(spec, last, rec.scalar_args)
    if a != b:
        raise LintError(
            f"{rec.name}: {what} tile moves across the sequence sweep "
            f"({a} → {b}) — not output-stationary")


def assert_scratch(
    rec: PallasRecord, expected: Sequence[tuple], label: str
) -> None:
    """Accumulators must carry exactly the declared running state."""
    got = [tuple(s.shape) for s in rec.scratch_shapes]
    want = [tuple(e) for e in expected]
    if got != want:
        raise LintError(
            f"{rec.name}: scratch accumulators {got} != declared running "
            f"state {want} ({label})")


def assert_s_independent(sigs: Sequence[tuple], name: str) -> None:
    """Scratch signatures probed at different sequence lengths must be
    identical — an accumulator scaling with S is an O(S) footprint."""
    if len(set(sigs)) != 1:
        raise LintError(
            f"{name}: accumulator shapes change with sequence length "
            f"({sigs}) — live footprint is not O(1)")


# ---------------------------------------------------------------------------
# jnp path tracing (shaped abstract values → pass counts)
# ---------------------------------------------------------------------------

@dataclass
class JnpTrace:
    passes: int
    #: shapes of tensors traversed in ≥ 2 distinct generations (O(S) live)
    multi_gen: list


@dataclass
class _Info:
    avail: int = 0
    ready: int = 0


_CALL_JAXPR_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr")


def _sub_jaxpr(params):
    for key in _CALL_JAXPR_KEYS:
        sub = params.get(key)
        if sub is not None:
            return sub
    return None


def trace_m_passes(
    fn: Callable,
    args: Sequence,
    *,
    m_total: int,
    m_pairs: Sequence[tuple] = (),
) -> JnpTrace:
    """Count passes over the sequence axis in a jnp implementation.

    ``m_total`` is the (distinctively-sized) sequence extent of the probe
    shapes; ``m_pairs`` lists (n_blocks, block) factorizations used by
    blocked layouts — a tensor carrying both factors covers the full
    sequence, one carrying a single factor is partial bookkeeping.
    Probe shapes must keep all other axis sizes distinct from these.
    """
    m_pairs = tuple(tuple(p) for p in m_pairs)
    part_sizes = {d for p in m_pairs for d in p}

    def is_full(shape) -> bool:
        if m_total in shape:
            return True
        return any(a in shape and b in shape for a, b in m_pairs)

    def is_partial(shape) -> bool:
        return (not is_full(shape)) and any(d in shape for d in part_sizes)

    def has_m(shape) -> bool:
        return is_full(shape) or is_partial(shape)

    jaxpr = jax.make_jaxpr(fn)(*args)
    env: dict = {}
    notes: dict = {}

    def note(var, gen: int) -> None:
        notes.setdefault(var, set()).add(gen)

    def shape_of(atom):
        return tuple(getattr(atom.aval, "shape", ()))

    def read(atom) -> _Info:
        if isinstance(atom, Literal):
            return _Info(0, 0)
        return env.get(atom, _Info(0, 0))

    def run(jx) -> None:
        for eqn in jx.eqns:
            prim = eqn.primitive.name
            if prim == "scan":
                _scan(eqn)
                continue
            sub = _sub_jaxpr(eqn.params)
            if sub is not None:
                inner = getattr(sub, "jaxpr", sub)
                n = len(inner.invars)
                for iv, a in zip(inner.invars, eqn.invars[-n:]):
                    env[iv] = read(a)
                run(inner)
                for ov, io in zip(eqn.outvars, inner.outvars):
                    env[ov] = read(io)
                    if is_full(shape_of(io)) and io in notes:
                        notes.setdefault(ov, set()).update(notes[io])
                continue
            _generic(eqn)

    def _generic(eqn) -> None:
        outs_m = any(has_m(shape_of(ov)) for ov in eqn.outvars)
        wait = 0
        traversed = []
        for a in eqn.invars:
            info = read(a)
            shp = shape_of(a)
            if is_full(shp):
                wait = max(wait, info.avail)
                traversed.append(a)
            elif is_partial(shp):
                wait = max(wait, info.avail if outs_m else info.ready)
            else:
                wait = max(wait, info.ready)
        full_reduce = bool(traversed) and not outs_m
        gen = wait + 1
        for a in traversed:
            if not isinstance(a, Literal):
                note(a, gen)
        avail = wait + 1 if full_reduce else wait
        ready = wait + 1 if traversed else wait
        out = _Info(avail, max(avail, ready))
        for ov in eqn.outvars:
            env[ov] = out
            if is_full(shape_of(ov)) and traversed:
                note(ov, gen)

    def _scan(eqn) -> None:
        # One iterative traversal: xs streaming the sequence axis are the
        # cascade's iterative rank; carries are running state, complete
        # (avail = ready) only once the sweep finishes.
        nc = eqn.params["num_consts"]
        ncar = eqn.params["num_carry"]
        wait = 0
        traversed = []
        for a in eqn.invars[: nc + ncar]:
            wait = max(wait, read(a).ready)
        for a in eqn.invars[nc + ncar:]:
            info = read(a)
            if is_full(shape_of(a)):
                wait = max(wait, info.avail)
                traversed.append(a)
            elif is_partial(shape_of(a)):
                wait = max(wait, info.avail)
            else:
                wait = max(wait, info.ready)
        iterates = bool(traversed)
        gen = wait + 1
        for a in traversed:
            if not isinstance(a, Literal):
                note(a, gen)
        out = _Info(gen, gen) if iterates else _Info(wait, wait)
        for ov in eqn.outvars:
            env[ov] = out
            if is_full(shape_of(ov)) and iterates:
                note(ov, gen)

    run(jaxpr.jaxpr)
    passes = max((g for gens in notes.values() for g in gens), default=0)
    multi = sorted(
        {shape_of(v) for v, gens in notes.items() if len(gens) > 1}
    )
    return JnpTrace(passes=passes, multi_gen=multi)


def assert_jnp_path(
    fn: Callable,
    args: Sequence,
    entry: CascadeEntry,
    *,
    m_total: int,
    m_pairs: Sequence[tuple] = (),
    label: str = "",
) -> JnpTrace:
    """Trace a jnp implementation and match it against its declaration."""
    tr = trace_m_passes(fn, args, m_total=m_total, m_pairs=m_pairs)
    name = f"{entry.name}[{label}]" if label else entry.name
    if tr.passes != entry.expected_passes:
        raise LintError(
            f"{name}: jnp path performs {tr.passes} passes over the "
            f"sequence, declaration says {entry.expected_passes}")
    if entry.footprint == O1 and tr.multi_gen:
        raise LintError(
            f"{name}: declared O(1) live footprint but tensors of shape "
            f"{tr.multi_gen} stay live across a pass barrier")
    if entry.footprint == OS and not tr.multi_gen:
        raise LintError(
            f"{name}: declared O(S) footprint but no full fiber crosses "
            f"a pass barrier — declaration is too pessimistic")
    return tr


# ---------------------------------------------------------------------------
# Probes: one per (kernel family, implementation path)
# ---------------------------------------------------------------------------

_LANES = 128


def _probe_prefill_pallas(entry: CascadeEntry) -> dict:
    from repro.kernels.fusemax import fusemax_attention_pallas
    sigs = []
    for m in (256, 512):
        with capture_pallas_calls() as recs:
            fusemax_attention_pallas(
                jnp.zeros((2, 128, 32), jnp.float32),
                jnp.zeros((2, m, 32), jnp.float32),
                jnp.zeros((2, m, 32), jnp.float32),
                scale=0.125, block_q=128, block_k=128)
        (rec,) = recs
        fixed = {0: rec.grid[0] - 1, 1: rec.grid[1] - 1}
        assert_single_sweep(rec, 1, fixed, m // 128, what="K")
        assert_single_sweep(rec, 2, fixed, m // 128, what="V")
        assert_stationary(rec, 0, sweep_axis=2, fixed=fixed, what="Q")
        assert_scratch(rec, [(128, _LANES), (128, _LANES), (128, 32)],
                       "RM/RD/RNV")
        sigs.append(rec.scratch_sig())
    assert_s_independent(sigs, entry.name)
    return {"probe": "pallas:prefill", "kernel": rec.name,
            "grid": rec.grid, "scratch": [s[0] for s in rec.scratch_sig()]}


def _probe_decode_pallas(entry: CascadeEntry, p: int = 1) -> dict:
    from repro.kernels.decode import fusemax_decode_pallas
    hkv, g, e, f, block_k, splits = 2, 8, 16, 16, 32, 2
    sigs = []
    for mp, lens in ((128, (100, 48)), (256, (200, 250))):
        kv_len = jnp.array(lens, jnp.int32)
        with capture_pallas_calls() as recs:
            fusemax_decode_pallas(
                jnp.zeros((2 * hkv, g, e), jnp.float32),
                jnp.zeros((2 * hkv, mp, e), jnp.float32),
                jnp.zeros((2 * hkv, mp, f), jnp.float32),
                kv_len, scale=0.25, hkv=hkv, splits=splits,
                block_k=block_k, p=p)
        (rec,) = recs
        split_len = mp // splits

        for bh in range(rec.grid[0]):
            limit = int(lens[bh // hkv]) + (p - 1)

            def live(b, s, m2, _lim=limit):
                return s * split_len + m2 * block_k < _lim

            n_tiles = -(-limit // block_k)
            assert_single_sweep(rec, 1, {0: bh}, n_tiles, live, "K")
            assert_single_sweep(rec, 2, {0: bh}, n_tiles, live, "V")
            assert_stationary(rec, 0, sweep_axis=2, fixed={0: bh, 1: 0})
        assert_scratch(rec, [(g, _LANES), (g, _LANES), (g, f)], "RM/RD/RNV")
        sigs.append(rec.scratch_sig())
    assert_s_independent(sigs, entry.name)
    return {"probe": f"pallas:decode[p={p}]", "kernel": rec.name,
            "grid": rec.grid, "scratch": [s[0] for s in rec.scratch_sig()]}


# paged kernels read one layer of pools stacked over a run's layers
_LAYERS, _LAYER = 3, 1


def _probe_decode_paged_pallas(
    entry: CascadeEntry, p: int = 1, quantized: bool = False
) -> dict:
    from repro.kernels.decode import fusemax_decode_paged_pallas
    hkv, g, e, f, page_size = 2, 8, 16, 16, 32
    # quantized pools sweep whole pages (their scale tiles put block_k on
    # the lane axis)
    block_k = page_size if quantized else 16
    sigs = []
    for w, lens in ((4, (70, 123)), (8, (150, 247))):
        n_pages = 2 * w + 1
        sentinel = n_pages
        table = np.full((2, w), sentinel, np.int32)
        for b, ln in enumerate(lens):
            used = -(-ln // page_size)
            table[b, :used] = np.arange(used) + b * w
        kv_len = jnp.array(lens, jnp.int32)
        kwargs = {}
        if quantized:
            kwargs = dict(
                k_scale=jnp.ones((n_pages, hkv, page_size), jnp.float32),
                v_scale=jnp.ones((n_pages, hkv, page_size), jnp.float32))
        kwargs = {k: jnp.stack([v] * _LAYERS) for k, v in kwargs.items()}
        with capture_pallas_calls() as recs:
            fusemax_decode_paged_pallas(
                jnp.zeros((2 * hkv, g, e), jnp.float32),
                jnp.zeros((_LAYERS, n_pages, hkv, page_size, e),
                          jnp.float32),
                jnp.zeros((_LAYERS, n_pages, hkv, page_size, f),
                          jnp.float32),
                jnp.asarray(table), kv_len, _LAYER, scale=0.25, hkv=hkv,
                splits=2, block_k=block_k, p=p, **kwargs)
        (rec,) = recs
        split_len = (w // 2) * page_size

        # one grid row per sequence: every kv head rides the same tile
        for b in range(rec.grid[0]):
            limit = int(lens[b]) + (p - 1)

            def live(b_i, s, m2, _lim=limit):
                return s * split_len + m2 * block_k < _lim

            n_tiles = -(-limit // block_k)
            streams = [(1, "K"), (2, "V")]
            if quantized:
                streams += [(3, "k_scale"), (4, "v_scale")]
            for si, what in streams:
                assert_single_sweep(rec, si, {0: b}, n_tiles, live, what)
                assert_one_layer(rec, si, {0: b}, _LAYER, what)
            assert_stationary(rec, 0, sweep_axis=2, fixed={0: b, 1: 0})
        assert_scratch(rec, [(hkv, g, _LANES), (hkv, g, _LANES),
                             (hkv, g, f)], "RM/RD/RNV per kv head")
        sigs.append(rec.scratch_sig())
    assert_s_independent(sigs, entry.name)
    return {"probe": f"pallas:decode_paged[p={p},quant={quantized}]",
            "kernel": rec.name, "grid": rec.grid,
            "scratch": [s[0] for s in rec.scratch_sig()]}


def _probe_mla_decode_paged_pallas(entry: CascadeEntry, p: int = 1) -> dict:
    from repro.kernels.decode import fusemax_mla_decode_paged_pallas
    g, rank, rope, page_size, block_k = 8, 16, 8, 32, 16
    sigs = []
    for w, lens in ((4, (70, 123)), (8, (150, 247))):
        n_pages = 2 * w + 1
        sentinel = n_pages
        table = np.full((2, w), sentinel, np.int32)
        for b, ln in enumerate(lens):
            used = -(-ln // page_size)
            table[b, :used] = np.arange(used) + b * w
        kv_len = jnp.array(lens, jnp.int32)
        with capture_pallas_calls() as recs:
            fusemax_mla_decode_paged_pallas(
                jnp.zeros((2, g, rank + rope), jnp.float32),
                jnp.zeros((_LAYERS, n_pages, page_size, rank), jnp.float32),
                jnp.zeros((_LAYERS, n_pages, page_size, rope), jnp.float32),
                jnp.asarray(table), kv_len, _LAYER, scale=0.25,
                splits=2, block_k=block_k, p=p)
        (rec,) = recs
        split_len = (w // 2) * page_size

        for b in range(rec.grid[0]):
            limit = int(lens[b]) + (p - 1)

            def live(b_i, s, m2, _lim=limit):
                return s * split_len + m2 * block_k < _lim

            n_tiles = -(-limit // block_k)
            assert_single_sweep(rec, 1, {0: b}, n_tiles, live, "CKV")
            assert_single_sweep(rec, 2, {0: b}, n_tiles, live, "KROPE")
            assert_one_layer(rec, 1, {0: b}, _LAYER, "CKV")
            assert_one_layer(rec, 2, {0: b}, _LAYER, "KROPE")
            assert_stationary(rec, 0, sweep_axis=2, fixed={0: b, 1: 0})
        # the [G, r] latent accumulator — the declared MLA running state
        assert_scratch(rec, [(g, _LANES), (g, _LANES), (g, rank)],
                       "RM/RD + [G, r] latent RNV")
        sigs.append(rec.scratch_sig())
    assert_s_independent(sigs, entry.name)
    return {"probe": f"pallas:mla_decode_paged[p={p}]", "kernel": rec.name,
            "grid": rec.grid, "scratch": [s[0] for s in rec.scratch_sig()]}


_M = 144                    # probe sequence extent (3 blocks of 48)
_PAIRS = ((3, 48),)


def _probe_jnp_ref(entry: CascadeEntry) -> dict:
    from repro.kernels.ref import mha_reference
    args = (jnp.zeros((2, 4, 5, 8), jnp.float32),
            jnp.zeros((2, 2, _M, 8), jnp.float32),
            jnp.zeros((2, 2, _M, 8), jnp.float32))
    tr = assert_jnp_path(mha_reference, args, entry, m_total=_M,
                         label="mha_reference")
    return {"probe": "jnp:mha_reference", "passes": tr.passes,
            "multi_gen": tr.multi_gen}


def _probe_jnp_decode_ref(entry: CascadeEntry) -> dict:
    from repro.kernels.ref import decode_reference
    args = (jnp.zeros((2, 4, 1, 8), jnp.float32),
            jnp.zeros((2, 2, _M, 8), jnp.float32),
            jnp.zeros((2, 2, _M, 8), jnp.float32),
            jnp.array([100, 40], jnp.int32))
    tr = assert_jnp_path(decode_reference, args, entry, m_total=_M,
                         label="decode_reference")
    return {"probe": "jnp:decode_reference", "passes": tr.passes,
            "multi_gen": tr.multi_gen}


def _probe_jnp_flash(entry: CascadeEntry) -> dict:
    from repro.kernels.ops import _make_flash_jnp
    flash = _make_flash_jnp(False, None, None, 0.125, 0, 48)
    args = (jnp.zeros((2, 2, 2, 5, 8), jnp.float32),
            jnp.zeros((2, 2, _M, 8), jnp.float32),
            jnp.zeros((2, 2, _M, 8), jnp.float32))
    tr = assert_jnp_path(flash, args, entry, m_total=_M, m_pairs=_PAIRS,
                         label="flash")
    return {"probe": "jnp:flash", "passes": tr.passes,
            "multi_gen": tr.multi_gen}


def _probe_jnp_2pass(entry: CascadeEntry) -> dict:
    from repro.core.cascades_numeric import attention_2pass
    args = (jnp.zeros((2, 4, 5, 8), jnp.float32),
            jnp.zeros((2, 4, _M, 8), jnp.float32),
            jnp.zeros((2, 4, _M, 8), jnp.float32))
    tr = assert_jnp_path(
        lambda q, k, v: attention_2pass(q, k, v, block=48), args, entry,
        m_total=_M, m_pairs=_PAIRS, label="attention_2pass")
    return {"probe": "jnp:attention_2pass", "passes": tr.passes,
            "multi_gen": tr.multi_gen}


def _probe_jnp_decode_splitk(entry: CascadeEntry) -> dict:
    from repro.kernels.ops import _decode_splitk_jnp
    args = (jnp.zeros((2, 4, 1, 8), jnp.float32),
            jnp.zeros((2, 2, _M, 8), jnp.float32),
            jnp.zeros((2, 2, _M, 8), jnp.float32),
            jnp.array([100, 40], jnp.int32))
    tr = assert_jnp_path(
        lambda *a: _decode_splitk_jnp(
            *a, scale=0.25, softcap=None, window=None, splits=3),
        args, entry, m_total=_M, m_pairs=_PAIRS, label="decode_splitk")
    return {"probe": "jnp:decode_splitk", "passes": tr.passes,
            "multi_gen": tr.multi_gen}


def _probe_jnp_verify_splitk(entry: CascadeEntry) -> dict:
    from repro.kernels.ops import _verify_splitk_jnp
    args = (jnp.zeros((2, 4, 2, 8), jnp.float32),
            jnp.zeros((2, 2, _M, 8), jnp.float32),
            jnp.zeros((2, 2, _M, 8), jnp.float32),
            jnp.array([100, 40], jnp.int32))
    tr = assert_jnp_path(
        lambda *a: _verify_splitk_jnp(*a, scale=0.25, softcap=None,
                                      splits=3),
        args, entry, m_total=_M, m_pairs=_PAIRS, label="verify_splitk")
    return {"probe": "jnp:verify_splitk", "passes": tr.passes,
            "multi_gen": tr.multi_gen}


def _probe_jnp_mla(entry: CascadeEntry, p: int = 1) -> dict:
    from repro.kernels.ops import (
        mla_combine_partials, mla_decode_partials,
        mla_verify_combine, mla_verify_partials,
    )

    def fn(q_cat, ckv, krope, kv_len):
        if p == 1:
            pm, pl_, pnv = mla_decode_partials(
                q_cat, ckv, krope, kv_len, start_page=0, n_splits=3,
                page_size=48, scale=0.25)
            return mla_combine_partials(pm, pl_, pnv, jnp.float32)
        pm, pl_, pnv = mla_verify_partials(
            q_cat, ckv, krope, kv_len, start_page=0, n_splits=3,
            page_size=48, scale=0.25)
        return mla_verify_combine(pm, pl_, pnv, jnp.float32)

    args = (jnp.zeros((2, 4, p, 24), jnp.float32),
            jnp.zeros((2, _M, 16), jnp.float32),
            jnp.zeros((2, _M, 8), jnp.float32),
            jnp.array([100, 40], jnp.int32))
    tr = assert_jnp_path(fn, args, entry, m_total=_M, m_pairs=_PAIRS,
                         label=f"mla[p={p}]")
    return {"probe": f"jnp:mla[p={p}]", "passes": tr.passes,
            "multi_gen": tr.multi_gen}


PROBES: dict[str, Callable[[CascadeEntry], dict]] = {
    "pallas:prefill": _probe_prefill_pallas,
    "pallas:decode": _probe_decode_pallas,
    "pallas:decode_paged": _probe_decode_paged_pallas,
    "pallas:decode_paged_quantized": functools.partial(
        _probe_decode_paged_pallas, quantized=True),
    "pallas:mla_decode_paged": _probe_mla_decode_paged_pallas,
    "pallas:verify_paged": functools.partial(
        _probe_decode_paged_pallas, p=2),
    "pallas:mla_verify_paged": functools.partial(
        _probe_mla_decode_paged_pallas, p=2),
    "jnp:mha_reference": _probe_jnp_ref,
    "jnp:decode_reference": _probe_jnp_decode_ref,
    "jnp:flash": _probe_jnp_flash,
    "jnp:attention_2pass": _probe_jnp_2pass,
    "jnp:decode_splitk": _probe_jnp_decode_splitk,
    "jnp:verify_splitk": _probe_jnp_verify_splitk,
    "jnp:mla_decode": _probe_jnp_mla,
    "jnp:mla_verify": functools.partial(_probe_jnp_mla, p=2),
}


def lint_entry(entry: CascadeEntry) -> list[dict]:
    """Run every structural probe bound to a registry entry.  Raises
    :class:`LintError` on the first declaration/implementation mismatch."""
    results = []
    for key in entry.lint:
        probe = PROBES.get(key)
        if probe is None:
            raise LintError(
                f"{entry.name}: lint probe '{key}' is not implemented — "
                f"declare the probe in repro.analysis.lint.PROBES")
        results.append(probe(entry))
    return results


def lint_all(
    entries: Optional[Iterable[CascadeEntry]] = None,
) -> list[dict]:
    """Lint every registry entry; returns per-entry result dicts with
    ``ok``/``error`` fields instead of raising (report/CI use)."""
    out = []
    for e in (REGISTRY if entries is None else entries):
        try:
            out.append({"name": e.name, "ok": True,
                        "probes": lint_entry(e)})
        except LintError as err:
            out.append({"name": e.name, "ok": False, "error": str(err)})
    return out


__all__ = [
    "JnpTrace",
    "LintError",
    "PROBES",
    "PallasRecord",
    "assert_jnp_path",
    "assert_s_independent",
    "assert_scratch",
    "assert_single_sweep",
    "assert_stationary",
    "capture_pallas_calls",
    "lint_all",
    "lint_entry",
    "tile_visits",
    "trace_m_passes",
]
