"""``factorize_device_share`` on the CPU: the reader against the program's
own registry, and against a server that has no such counter."""
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import run  # noqa: E402

read = run.reader("factorize_device_share")


def _counter(**values):
    return {"factorize_total": {"kind": "counter", "values": values}}


@pytest.mark.parametrize("before, after, want", [
    ({}, {}, None),                                  # parent: no counter
    (_counter(), _counter(), None),                  # nothing factorized
    (_counter(device=3.0), _counter(device=3.0), None),
    (_counter(device=3.0), _counter(device=7.0), 1.0),
    (_counter(device=1.0, host=1.0), _counter(device=4.0, host=2.0), 0.75),
    (_counter(host=2.0), _counter(host=5.0), 0.0),
])
def test_share_of_the_window(before, after, want):
    rec = SimpleNamespace(counters0=before, counters1=after)
    assert read(rec) == want


def test_reads_the_scheduler_registry():
    from repro.service import Scheduler
    sched = Scheduler()
    rng = np.random.default_rng(0)
    X = rng.normal(size=(300, 3)).astype(np.float32)
    y = rng.integers(0, 2, 300)
    c0 = sched.metrics.to_dict()
    bad = X.copy()
    bad[0, 0] = np.nan
    for table in (X, X[::-1].copy(), bad):
        sched._factorize(sched.jobs[sched.submit(table, y)])
    rec = SimpleNamespace(counters0=c0, counters1=sched.metrics.to_dict())
    assert read(rec) == pytest.approx(2 / 3)
