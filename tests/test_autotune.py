"""Autotuner: modeled table picks feasible params; measured mode wins."""
import time

import pytest

from repro.kernels import autotune


@pytest.fixture(autouse=True)
def fresh_table():
    autotune.clear_table()
    yield
    autotune.clear_table()


def test_attention_params_feasible_across_shapes():
    for p, m, e, f in [(1, 64, 32, 32), (128, 256, 64, 64),
                       (4096, 4096, 128, 128), (100, 200, 48, 48)]:
        t = autotune.attention_params(p, m, e, f)
        assert t.block_q > 0 and t.block_k > 0
        assert autotune._attention_cost(t, p, m, e, f) < float("inf")


def test_decode_params_divide_cache_length():
    for m in (64, 100, 256, 2048, 8192):
        t = autotune.decode_params(m, 8, 64, 64)
        assert t.splits >= 1 and m % t.splits == 0
        assert t.block_k >= 1


def test_longer_cache_gets_more_splits():
    short = autotune.decode_params(256, 8, 64, 64)
    long = autotune.decode_params(16384, 8, 64, 64)
    assert long.splits >= short.splits


def test_table_caches_lookups():
    t1 = autotune.attention_params(128, 256, 64, 64)
    t2 = autotune.attention_params(128, 256, 64, 64)
    assert t1 == t2
    assert len(autotune._TABLE) == 1
    # same pow2 bucket → same entry, no second modeling pass
    autotune.attention_params(120, 250, 64, 64)
    assert len(autotune._TABLE) == 1


def test_measure_best_picks_faster_candidate_and_seeds_table():
    def make_fn(cand):
        delay = 0.02 if cand.splits == 1 else 0.0

        def fn():
            time.sleep(delay)
            return None

        return fn

    cands = [autotune.DecodeParams(1, 128), autotune.DecodeParams(4, 128)]
    key = ("decode", "cpu", "jnp", "256", "8", "64", "64")
    best, timings = autotune.measure_best(make_fn, cands, key=key,
                                          iters=2, warmup=0)
    assert best == cands[1]
    assert timings[cands[0]] > timings[cands[1]]
    # the measured winner now backs the table lookup
    hit = autotune.decode_params(256, 8, 64, 64)
    assert (hit.splits, hit.block_k) == (4, 128)


def test_disk_cache_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    autotune.clear_table()

    def make_fn(cand):
        return lambda: None

    key = ("decode", "cpu", "jnp", "512", "8", "32", "32")
    best, _ = autotune.measure_best(
        make_fn, [autotune.DecodeParams(2, 256)], key=key, iters=1, warmup=0)
    autotune.clear_table()
    hit = autotune.decode_params(512, 8, 32, 32)
    assert (hit.splits, hit.block_k) == (2, 256)


def test_measure_best_names_skipped_candidate(caplog):
    """An infeasible candidate is skipped, and the warning says which one
    and why."""
    autotune.clear_table()
    good, bad = autotune.DecodeParams(1, 128), autotune.DecodeParams(2, 64)

    def make_fn(cand):
        if cand == bad:
            raise ValueError("block does not fit")
        return lambda: None

    with caplog.at_level("WARNING", logger="repro.kernels.autotune"):
        best, timings = autotune.measure_best(make_fn, [bad, good],
                                              iters=1, warmup=0)
    assert best == good and timings[bad] == float("inf")
    assert repr(bad) in caplog.text and "block does not fit" in caplog.text
