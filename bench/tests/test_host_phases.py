"""CPU tests of what the benchmark reads of the serving engine's host
phases: the ``host_ms_per_dispatch`` reader on hand-built records, the
counters in the log of a whole run, and the trace reduction with the
program's ``engine.*`` spans in the trace.  No number here is a device
metric."""
from __future__ import annotations

import dataclasses
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, registry  # noqa: E402
from bench import trace as trace_mod  # noqa: E402
from bench.tests import tiny  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "v5e_decode_prefill.xspace.pbtxt")
SEED = 2 ** 31 + 4242
CELL = "stablelm-1.6b.decode-heavy"
KERNELS = registry.arch({"arch": "dense"}).KERNELS


def _stats(**kw):
    s = {"decode_dispatches": 0, "prefill_dispatches": 0,
         "decode_steps": 0, "tokens_decoded": 0}
    s.update(kw)
    return s


def _read(name, rec):
    return registry.metric_reader(name)(rec)


# -- the reader ---------------------------------------------------------------

def test_host_ms_per_dispatch_reads_the_window_deltas():
    # 12 decode + 3 prefill dispatches; 150 ms in the turns, 90 of them
    # blocked on the chip: 60 ms of host work over 15 dispatches
    rec = {"stats": _stats(decode_dispatches=12, prefill_dispatches=3,
                           host_ns_pump=150_000_000,
                           host_ns_decode_wait=90_000_000,
                           host_ns_admit=5_000_000)}
    assert _read("host_ms_per_dispatch", rec) == pytest.approx(4.0)


@pytest.mark.parametrize("stats", [
    _stats(host_ns_pump=0, host_ns_decode_wait=0),      # no dispatch
    _stats(decode_dispatches=4, prefill_dispatches=1),  # no such counters
], ids=["no-dispatch", "program-without-counters"])
def test_host_ms_per_dispatch_finds_nothing_to_read(stats):
    assert _read("host_ms_per_dispatch", {"stats": stats}) is None


def test_whole_run_logs_the_phase_counters(tmp_path, capsys):
    """The counters are engine stats, so every run, traced or not, logs
    their window deltas with the other engine counters."""
    root = tiny.make_root(str(tmp_path))
    out = harness.run(root, "tiny.decode", SEED, 1.0, False,
                      harness.Options(interpret=True))
    assert out["correct"] is True
    line = next(ln for ln in capsys.readouterr().out.splitlines()
                if ln.startswith("engine counters in the window:"))
    got = dict(re.findall(r"(host_ns_\w+) (\d+)", line))
    for phase in ("pump", "admit", "prefill", "decode_prepare",
                  "decode_launch", "decode_wait", "decode_harvest"):
        assert int(got["host_ns_" + phase]) > 0, phase
    assert int(got["host_ns_pump"]) >= sum(
        int(v) for k, v in got.items() if k != "host_ns_pump")


# -- the trace reduction with engine spans in the trace -----------------------

def _load(text):
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(text)


def _fixture_text():
    with open(FIXTURE) as f:
        return f.read().split("\n", 1)[1]          # drop the comment line


def _longest_gap(pd):
    """Start and end (ns) of the longest stretch of the window in which no
    op runs on the chip."""
    host = pd.find_plane_with_name("/host:CPU")
    (lo, hi), = [(e.start_ns, e.start_ns + e.duration_ns)
                 for ln in host.lines for e in ln.events
                 if e.name == "bench.window"]
    dev = pd.find_plane_with_name("/device:TPU:0")
    ops = sorted((e.start_ns, e.start_ns + e.duration_ns)
                 for ln in dev.lines if ln.name == "XLA Ops"
                 for e in ln.events)
    best, prev = (0.0, lo, lo), lo
    for s, e in ops + [(hi, hi)]:
        s = min(max(s, lo), hi)
        if s - prev > best[0]:
            best = (s - prev, prev, s)
        prev = max(prev, min(e, hi))
    return best[1], best[2]


def _with_engine_span(text, lo, hi, name="engine.decode.harvest"):
    """The fixture with one host span ``name`` over ``[lo, hi)`` (ns) on
    the host's ``python`` line, beside the ``bench.*`` spans."""
    meta = 'event_metadata { key: 277 value { id: 277 name: "bench.pump" } }'
    last = list(re.finditer(r"    events \{ metadata_id: 277 [^\n]*\n",
                            text))[-1]
    event = (f"    events {{ metadata_id: 278 offset_ps: {int(lo * 1000)} "
             f"duration_ps: {int((hi - lo) * 1000)} }}\n")
    text = text[:last.end()] + event + text[last.end():]
    return text.replace(meta, meta + "\n  event_metadata { key: 278 value "
                        f'{{ id: 278 name: "{name}" }} }}')


@pytest.fixture(scope="module")
def traces():
    text = _fixture_text()
    lo, hi = _longest_gap(_load(text))
    return _load(text), _load(_with_engine_span(text, lo, hi)), hi - lo


def _fields(red, labels=True):
    d = dataclasses.asdict(red)
    if not labels:
        d["idle_gaps"] = [t for _, t in d["idle_gaps"]]
    return d


def test_engine_spans_leave_the_reduction_unchanged(traces):
    plain, spanned, gap_ns = traces
    a = trace_mod.reduce(plain, KERNELS)
    b = trace_mod.reduce(spanned, KERNELS)
    assert a.idle_gaps[0][1] == pytest.approx(gap_ns * 1e-9)
    assert _fields(a, labels=False) == _fields(b, labels=False)
    assert a.idle_frac == b.idle_frac


def test_a_gap_inside_an_engine_phase_takes_its_label(traces):
    """The reduction collects the program's ``engine.*`` spans beside its
    own: the longest gap takes the innermost span's label and every other
    field of the reduction reads as before."""
    plain, spanned, _ = traces
    before = trace_mod.reduce(plain, KERNELS)
    after = trace_mod.reduce(spanned, KERNELS)
    assert after.idle_gaps[0][0] == "engine.decode.harvest"
    assert before.idle_gaps[0][0] == "bench.pump"
    assert [x for x, _ in after.idle_gaps[1:3]] == ["bench.pump"] * 2
    assert _fields(before, labels=False) == _fields(after, labels=False)


def test_engine_spans_leave_every_metric_unchanged(traces):
    plain, spanned, _ = traces
    cell = registry.load_cell(ROOT, CELL)
    rec = {"stats": _stats(decode_dispatches=2, prefill_dispatches=2,
                           decode_steps=17, tokens_decoded=130,
                           tokens_prefilled=320),
           "slots": 8, "shapes": cell.arch.Shapes.from_config(cell.config),
           "peak": registry.peaks("TPU v5 lite"),
           "decode_ctx": [200 + i for i in range(130)],
           "prefills": [(0, 256), (0, 64)], "decode_hbm_bytes": 2 ** 33}
    a = registry.read_metrics(
        cell, dict(rec, trace=trace_mod.reduce(plain, KERNELS)))
    b = registry.read_metrics(
        cell, dict(rec, trace=trace_mod.reduce(spanned, KERNELS)))
    assert a == b
    assert {"decode_step_ms", "paged_decode_roofline",
            "device_idle_frac"} <= set(a)
    # a program without the phase counters reads no host time
    assert "host_ms_per_dispatch" not in a
