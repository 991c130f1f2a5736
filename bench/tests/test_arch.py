"""CPU tests of the architecture modules (``bench/arch/<arch>.py``): the
per-layer readers and the reference read what they read while the dense
arithmetic lived in the harness, the trace matches a kernel by its name,
and a second architecture enters by new files alone.  Smoke sizes and a
recorded trace only; no number here is a device metric."""
from __future__ import annotations

import importlib.util
import os
import re
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, reference, registry  # noqa: E402
from bench import trace as trace_mod  # noqa: E402
from bench.tests import tiny  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
FIXTURE = os.path.join(DATA, "v5e_decode_prefill.xspace.pbtxt")
SEED = 2 ** 31 + 12345
CELL = "stablelm-1.6b.decode-heavy"
DENSE = registry.arch({"arch": "dense"})


def _fixture_text() -> str:
    with open(FIXTURE) as f:
        return f.read().split("\n", 1)[1]          # drop the comment line


def _load(text: str):
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(text)


# -- every reader reads what it read before the move ------------------------

#: what each reader of ``stablelm-1.6b.decode-heavy`` read on the recorded
#: trace and the record below while the work counts, the K/V count and the
#: kernel table were the harness's own (dense only), to the last bit
READ_BEFORE = {
    "batch_occupancy": 0.9558823529411765,
    "decode_step_ms": 1.8852061764705885,
    "model_mfu": 12.454603240609137,
    "decode_mfu": 6.03159974921801,
    "paged_decode_roofline": 423.0818805963682,
    "device_idle_frac": 0.21129908333333325,
    "decode_hbm_gib": 8.0,
    "host_ms_per_dispatch": 15.0,
}


@pytest.fixture(scope="module")
def read_now():
    cell = registry.load_cell(ROOT, CELL)
    rec = {"stats": {"decode_dispatches": 2, "prefill_dispatches": 2,
                     "decode_steps": 17, "tokens_decoded": 130,
                     "tokens_prefilled": 320, "host_ns_pump": 150_000_000,
                     "host_ns_decode_wait": 90_000_000},
           "slots": 8, "shapes": cell.arch.Shapes.from_config(cell.config),
           "peak": registry.peaks("TPU v5 lite"),
           "decode_ctx": [200 + i for i in range(130)],
           "prefills": [(0, 256), (0, 64)], "decode_hbm_bytes": 2 ** 33,
           "trace": trace_mod.reduce(_load(_fixture_text()),
                                     cell.arch.KERNELS)}
    return {k: v["value"] for k, v in registry.read_metrics(cell,
                                                            rec).items()}


@pytest.mark.parametrize("metric", sorted(READ_BEFORE))
def test_reader_reads_what_it_read_before_the_move(read_now, metric):
    assert read_now[metric] == READ_BEFORE[metric]


def test_every_reader_of_the_cell_is_pinned(read_now):
    cell = registry.load_cell(ROOT, CELL)
    assert set(read_now) == {m["name"] for m in cell.per_layer} \
        == set(READ_BEFORE)


def _reference_before():
    path = os.path.join(DATA, "reference_dense_v1.py")
    spec = importlib.util.spec_from_file_location("bench_reference_v1",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod          # its dataclass looks itself up
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("norm,kv_heads", [("layernorm", 4),
                                           ("rmsnorm", 2)])
def test_reference_logits_are_unchanged_by_the_move(norm, kv_heads):
    """The reference through the dense module gives, bit for bit, the
    logits of the reference that held the dense block itself, in float32
    and in the float8 control, on the tiny configurations of
    ``test_reference_matches_the_engine``."""
    config = dict(tiny.TINY_CONFIG, norm=norm, num_key_value_heads=kv_heads,
                  tie_word_embeddings=norm == "rmsnorm")
    toks = list(np.random.default_rng(0).integers(0, 256, (2, 48),
                                                  np.int32))
    before = _reference_before()
    arch = registry.arch(config)
    for precision in ("f32", "fp8"):
        want = before.logits_at(config, SEED, toks, [np.arange(48)] * 2, 64,
                                precision)
        got = reference.logits_at(arch, config, SEED, toks,
                                  [np.arange(48)] * 2, 64, precision)
        np.testing.assert_array_equal(np.stack(got), np.stack(want))


def test_kv_bits_counts_the_dense_pool_as_before():
    """A bf16 pool of the configuration's pages, layers, K and V, kv heads
    and head size reads 16 bits per element, as it did."""
    cell = registry.load_cell(ROOT, CELL)
    c, e = cell.config, cell.config["engine"]
    assert cell.arch.kv_elements(c) == 512 * 16 * 24 * 2 * 32 * 64
    pool = np.zeros((e["num_pages"], 32, e["page_size"], 64), np.float16)
    eng = types.SimpleNamespace(caches=[{"k": pool, "v": pool}] * 24)
    assert harness.kv_bits(eng, cell) == 16.0


# -- kernels are matched by name ---------------------------------------------

def _rename_one(text: str, kernel: str) -> tuple:
    """The fixture with the last ``fusemax_decode_paged`` call inside the
    decode program renamed to ``kernel``; returns the text and that
    call's duration (s)."""
    meta = re.search(r'event_metadata \{ key: 97 value \{ id: 97 name: '
                     r'"%fusemax_decode_paged\.7 = [^\n]*\n', text).group(0)
    last = list(re.finditer(r"events \{ metadata_id: 97 offset_ps: \d+ "
                            r"duration_ps: (\d+) \}", text))[-1]
    text = text[:last.start()] + last.group(0).replace(
        "metadata_id: 97 ", "metadata_id: 280 ") + text[last.end():]
    renamed = meta.replace("key: 97", "key: 280").replace(
        "id: 97", "id: 280").replace("fusemax_decode_paged.7",
                                     kernel + ".1")
    return text.replace(meta, meta + renamed), int(last.group(1)) * 1e-12


def test_a_kernel_entry_counts_only_the_ops_named_for_it():
    text = _fixture_text()
    before = trace_mod.reduce(_load(text), DENSE.KERNELS)
    renamed, dur = _rename_one(text, "fusemax_mla_decode_paged")
    table = dict(DENSE.KERNELS,
                 mla_decode=("decode", "fusemax_mla_decode_paged"),
                 mla_in_prefill=("prefill", "fusemax_mla_decode_paged"))
    after = trace_mod.reduce(_load(renamed), table)
    assert dur > 0
    assert after.kernels["mla_decode"] == pytest.approx(dur)
    assert "mla_in_prefill" not in after.kernels
    assert after.kernels["paged_decode"] == pytest.approx(
        before.kernels["paged_decode"] - dur)
    assert after.kernels["prefill_attention"] == \
        before.kernels["prefill_attention"]
    # the rename moves no other number of the reduction
    assert (after.busy_s, after.window_s, after.programs) == \
        (before.busy_s, before.window_s, before.programs)
    # a table without the kernel counts none of it
    assert trace_mod.reduce(_load(text), {}).kernels == {}


# -- a second architecture, by new files alone --------------------------------

#: a dense stack in other clothes: its layers alternate between two kinds,
#: its pool counts half dense's elements, its work counts are doubled and
#: its kernel table names its own entry
TWIN = '''"""Dense layers of two kinds, for the files-only test."""
import collections
import os

from bench import registry

dense = registry.arch({"arch": "dense"}, os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
CALLS = collections.Counter()
KERNELS = {"twin_decode": ("decode", "fusemax_decode_paged")}
Arch = dense.Arch


def model_config(config):
    CALLS["model_config"] += 1
    return dense.model_config(config)


def kv_elements(config):
    return dense.kv_elements(config) // 2


class Shapes(dense.Shapes):
    def decode_token_flops(self, ctx):
        return 2 * super().decode_token_flops(ctx)

    def prefill_flops(self, start, end):
        return 2 * super().prefill_flops(start, end)


def model_keys(seed, a):
    CALLS["model_keys"] += 1
    k_embed, k_head, layers = dense.model_keys(seed, a)
    return k_embed, k_head, [("even" if i % 2 == 0 else "odd", k)
                             for i, (_, k) in enumerate(layers)]


def layer_forward(a, kind, x, key, fp8=False):
    CALLS["layer_forward." + kind] += 1
    return dense.layer_forward(a, dense.BLOCK, x, key, fp8)
'''

#: readers a later cell of such an architecture would bring
PROBES = {
    "twin_kernel_s":
        "def read(rec):\n    return rec['trace'].kernels.get('twin_decode')\n",
    "twin_flops_10":
        "def read(rec):\n    return rec['shapes'].decode_token_flops(10)\n",
}


def test_a_second_architecture_enters_by_files_alone(tmp_path, monkeypatch):
    """A throwaway ``bench/arch/twin.py`` and a configuration naming it:
    the run builds the program's config, serves, reduces the trace,
    counts the work and the K/V bits, and checks against the reference,
    each through the twin, with no other file touched."""
    config = dict(tiny.TINY_CONFIG, arch="twin")
    with open(os.path.join(ROOT, "bench", "metrics",
                           "paged_decode_roofline.py")) as f:
        probes = dict(PROBES, paged_decode_roofline=f.read())
    root = tiny.make_root(str(tmp_path), config=config,
                          extra_arches={"twin": TWIN}, extra_metrics=probes)
    cell = registry.load_cell(root, "tiny.decode")
    twin = cell.arch
    assert twin is registry.arch(config, os.path.join(root, "bench"))
    assert not twin.CALLS
    # the CPU's trace holds no chip: the recorded v5e trace stands in
    monkeypatch.setattr(harness, "_xplane", lambda d: d)
    monkeypatch.setattr(trace_mod, "load",
                        lambda path: _load(_fixture_text()))
    out = harness.run(root, "tiny.decode", SEED, 1.0, True,
                      harness.Options(interpret=True))
    assert out["correct"] is True, out["checks"]
    # the program's config and the reference's layers, one compile a kind
    assert twin.CALLS["model_config"] == 1
    assert twin.CALLS["model_keys"] == 1
    assert twin.CALLS["layer_forward.even"] == 1
    assert twin.CALLS["layer_forward.odd"] == 1
    # half dense's elements: the bf16 pool reads 32 bits an element
    assert out["checks"]["kv_bits_short"]["value"] == 16 - 32
    # the kernel table and the work counts
    got = {k: v["value"] for k, v in out["metrics"].items()}
    want_s = trace_mod.reduce(_load(_fixture_text()),
                              DENSE.KERNELS).kernels["paged_decode"]
    assert got["twin_kernel_s"] == want_s
    assert got["twin_flops_10"] == 2 * DENSE.Shapes.from_config(
        config).decode_token_flops(10)
    # the dense kernel's reader finds no paged_decode in the twin's table
    assert "paged_decode_roofline" not in got


@pytest.mark.parametrize("arch", [None, "no_such_arch", "../metrics/x"],
                         ids=["missing", "unknown", "a-path"])
def test_a_configuration_must_name_a_known_architecture(tmp_path, arch):
    config = dict(tiny.TINY_CONFIG)
    config.pop("arch")
    if arch is not None:
        config["arch"] = arch
    root = tiny.make_root(str(tmp_path), config=config)
    with pytest.raises(KeyError, match=r"bench/configs/tiny\.json"):
        registry.load_cell(root, "tiny.chat")
