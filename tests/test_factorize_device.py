"""Factorize on the device (DESIGN.md §5.1): bit-identical to the host path,
taken only where float32 holds the table exactly, one sort program per row
bucket."""
import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

from repro.core import measures
from repro.core.measures import factorize_path, row_bucket
from repro.obs import jaxprof
from repro.service import Scheduler, dataset_fingerprint

BENCH = Path(__file__).resolve().parents[1] / "bench"


def _bench_table(config: str, n_rows: int, seed: int):
    spec = importlib.util.spec_from_file_location(
        "bench_tables", BENCH / "tables.py")
    tables = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tables)
    shape = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    return tables.make_table(dict(shape["table"], n_rows=n_rows), seed)


def _rng(seed=0):
    return np.random.default_rng(seed)


def _normal(n, m=2, seed=0):
    return _rng(seed).normal(size=(n, m)).astype(np.float32)


def _labels(n, k=2, seed=1):
    return _rng(seed).integers(0, k, n)


def _cardinality(n, k):
    """Two columns of exactly ``k`` distinct values."""
    X = _rng().integers(0, k, (n, 2)).astype(np.float32)
    X[:k, 0] = X[:k, 1] = np.arange(k)
    return X, _labels(n)


def _edge_ties(n):
    """Columns whose quantile thresholds fall inside and at the edges of
    long runs of equal values."""
    rng = _rng(3)
    runs = np.repeat(np.arange(300, dtype=np.float32), n // 300 + 1)[:n]
    geometric = rng.geometric(0.02, n).astype(np.float32)
    mostly_zero = np.where(rng.random(n) < 0.7, 0.0,
                           rng.normal(size=n)).astype(np.float32)
    return np.column_stack([runs, geometric, mostly_zero]), _labels(n)


def _signed_zeros(n):
    rng = _rng(4)
    dense = rng.normal(size=n).astype(np.float32)
    dense[::3], dense[1::3] = -0.0, 0.0
    few = rng.choice(np.array([-0.0, 0.0, 1.0, -1.0], np.float32), n)
    return np.column_stack([dense, few]), _labels(n)


PARITY_CASES = {
    "d1_scaled": lambda: _bench_table("d1-airline", 4000, 11),
    "d10_scaled": lambda: _bench_table("d10-poker", 6000, 12),
    "constant_column": lambda: (np.column_stack(
        [np.full(3000, 7.5, np.float32), _normal(3000, 1)[:, 0]]),
        _labels(3000)),
    "64_distinct": lambda: _cardinality(3000, 64),
    "65_distinct": lambda: _cardinality(3000, 65),
    "ties_on_quantile_edges": lambda: _edge_ties(5000),
    "n_minus_1_is_256": lambda: (_normal(257), _labels(257)),
    "n_minus_1_is_1024": lambda: (_normal(1025), _labels(1025)),
    "n_minus_1_not_multiple": lambda: (_normal(3001), _labels(3001)),
    "negative_zero": lambda: _signed_zeros(2000),
    "one_row": lambda: (np.array([[1.5, -2.0]], np.float32), np.array([3])),
    "two_rows": lambda: (np.array([[1.5, 2.0], [0.5, 2.0]], np.float32),
                         np.array([1, 0])),
    "target_100_classes": lambda: (_normal(3000), _labels(3000, k=100)),
    "no_target_3_chunks": lambda: (_normal(1500, 20), None),
    "integer_valued_float64": lambda: (
        _rng(5).integers(-9, 9, (2000, 3)).astype(np.float64),
        _labels(2000).astype(np.float64)),
}


def _assert_same(a, b):
    assert np.asarray(a.codes).dtype == np.asarray(b.codes).dtype
    assert np.array_equal(np.asarray(a.codes), np.asarray(b.codes))
    assert np.array_equal(np.asarray(a.n_bins), np.asarray(b.n_bins))
    assert a.max_bins == b.max_bins
    assert a.target_col == b.target_col
    assert np.asarray(a.values).tobytes() == np.asarray(b.values).tobytes()
    assert dataset_fingerprint(a) == dataset_fingerprint(b)


@pytest.mark.parametrize("case", sorted(PARITY_CASES))
def test_device_path_matches_host_path(case):
    X, y = PARITY_CASES[case]()
    coded, path = factorize_path(X, y)
    assert path == "device"
    _assert_same(coded, measures._factorize_host(
        np.asarray(X), None if y is None else np.asarray(y), 256, 64))


def _with_nan():
    X, y = _normal(1000), _labels(1000)
    X[17, 1] = np.nan
    return X, y


def _with_inf(sign):
    X, y = _normal(1000), _labels(1000)
    X[17, 0] = sign * np.inf
    return X, y


def _float64_merged():
    """float64 values that float32 rounds to one value."""
    X = _rng(6).normal(size=(1000, 2)).astype(np.float32).astype(np.float64)
    X[:500, 0] = 1.0
    X[500:, 0] = 1.0 + 1e-12
    return X, _labels(1000)


PATH_CASES = {
    "nan": (_with_nan, "host"),
    "pos_inf": (lambda: _with_inf(1.0), "host"),
    "neg_inf": (lambda: _with_inf(-1.0), "host"),
    "float64_merged_by_float32": (_float64_merged, "host"),
    "nan_label": (lambda: (_normal(1000), np.where(
        np.arange(1000) == 3, np.nan, _labels(1000))), "host"),
    "label_beyond_2_24": (lambda: (_normal(1000), _labels(1000) + 2**25),
                          "host"),
    "finite_float32": (lambda: (_normal(1000), _labels(1000)), "device"),
}


@pytest.mark.parametrize("case", sorted(PATH_CASES))
def test_path_choice_and_counter(case):
    make, want = PATH_CASES[case]
    X, y = make()
    coded, path = factorize_path(X, y)
    assert path == want
    _assert_same(coded, measures._factorize_host(X, y, 256, 64))

    sched = Scheduler()
    before = sched.metrics.to_dict()["factorize_total"].get("values", {})
    job = sched.jobs[sched.submit(X, y)]
    sched._factorize(job)
    after = sched.metrics.to_dict()["factorize_total"]["values"]
    assert after.get(want, 0) == before.get(want, 0) + 1
    assert sum(after.values()) == sum(before.values()) + 1
    assert job.fingerprint == dataset_fingerprint(coded)
    assert f'factorize_total{{path="{want}"}} 1' in sched.metrics.render()


def test_row_buckets():
    assert row_bucket(1) == row_bucket(4096) == 4096
    assert [row_bucket(n) for n in (4097, 5121, 6145, 7169, 8193)] == [
        5120, 6144, 7168, 8192, 10240]
    for n in (1, 4096, 4097, 103_904, 800_000, 3_000_001):
        b = row_bucket(n)
        assert b >= n and b % 1024 == 0
        assert b < 1.25 * n or b == 4096


def test_sort_program_traces_once_per_row_bucket():
    measures._factorize_chunk.clear_cache()
    site = "measures.factorize_device"
    snap = jaxprof.tracing_snapshot()
    rng = _rng(7)
    for n in (12_289, 13_000, 14_336):          # one bucket: 14,336
        for m in (1, 8, 19):                    # 1, 2 and 3 chunks
            X = rng.normal(size=(n, m)).astype(np.float32)
            assert factorize_path(X, rng.integers(0, 3, n))[1] == "device"
    assert jaxprof.new_tracings_since(snap).get(site) == 1
    X = rng.normal(size=(14_337, 4)).astype(np.float32)   # the next bucket
    factorize_path(X, None)
    assert jaxprof.new_tracings_since(snap).get(site) == 2
