"""``gen_dst_sort_share`` on the CPU: the reader on synthetic counter
snapshots, with and without the counter."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import run  # noqa: E402

read = run.reader("gen_dst_sort_share")


def _counter(**values):
    return {"gen_dst_total": {"kind": "counter", "values": values}}


@pytest.mark.parametrize("before, after, want", [
    ({}, {}, None),                                  # parent: no counter
    (_counter(), _counter(), None),                  # no search ran
    (_counter(sort=4.0), _counter(sort=4.0), None),
    (_counter(), _counter(sort=6.0), 1.0),
    (_counter(sort=2.0), _counter(sort=5.0), 1.0),
    (_counter(sort=1.0), _counter(sort=4.0, counts=1.0), 0.75),
    (_counter(pallas_fused=1.0), _counter(pallas_fused=3.0), 0.0),
])
def test_share_of_the_window(before, after, want):
    rec = SimpleNamespace(counters0=before, counters1=after)
    assert read(rec) == want
