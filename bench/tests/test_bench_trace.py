"""The trace reduction, on a small trace recorded on a TPU v5e and on a
hand-made one."""
import gzip
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import trace_reduce  # noqa: E402

# six seconds of d1.fresh on one TPU v5e: eight Gen-DST searches, the
# tables' factorize in between, one program compiling
RECORDED = BENCH / "tests" / "data" / "trace_d1_fresh.json.gz"


@pytest.fixture(scope="module")
def recorded():
    events = json.loads(gzip.decompress(RECORDED.read_bytes()))
    return {"devices": [[tuple(e) for e in dev] for dev in events["devices"]],
            "host": {k: [tuple(e) for e in v]
                     for k, v in events["host"].items()}}


def test_recorded_trace_reduces_to_known_numbers(recorded):
    red = trace_reduce.reduce(recorded)
    assert red["busy_s"] == pytest.approx(1.795622684, abs=1e-9)
    assert red["window_s"] == pytest.approx(6.758242269, abs=1e-9)
    assert red["idle_share"] == pytest.approx(0.7343062570815926, rel=1e-12)
    assert red["device_ops"][0] == ["jit__gen_dst_core",
                                    pytest.approx(1.614214752, abs=1e-9)]
    assert red["device_ops"][1][0] == "jit_full_column_entropy"
    assert red["idle_gaps"][0] == ["compiler.py:312 backend_compile_and_load",
                                   pytest.approx(2.102587713, abs=1e-9)]
    assert red["idle_gaps"][1][0] == "measures.py:80 factorize"
    assert len(red["idle_gaps"]) == 10


def test_recorded_gen_dst_programs(recorded):
    secs, count = trace_reduce.program_seconds(recorded, "jit__gen_dst_core")
    assert count == 8 and secs == pytest.approx(1.614214752, abs=1e-9)


def test_hand_made_trace():
    ms = 1_000_000
    events = {
        "devices": [[("jit_a(3)", 10 * ms, 20 * ms), ("jit_b", 15 * ms, 30 * ms),
                     ("jit_a", 60 * ms, 70 * ms)]],
        "host": {"python3": [("run", 0, 100 * ms),
                             ("$x.py:1 prep", 30 * ms, 58 * ms),
                             ("$y.py:2 jit_call", 9 * ms, 11 * ms),
                             ("$y.py:2 jit_call", 59 * ms, 61 * ms)],
                 "other": [("idle", 0, 100 * ms)]},
    }
    red = trace_reduce.reduce(events)
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.030)            # [10,30] + [60,70]
    assert red["idle_share"] == pytest.approx(0.7)
    assert red["device_ops"] == [["jit_a", pytest.approx(0.020)],
                                 ["jit_b", pytest.approx(0.015)]]
    assert red["idle_gaps"] == [["x.py:1 prep", pytest.approx(0.030)],
                                ["run", pytest.approx(0.030)],   # [70, 100]
                                ["run", pytest.approx(0.010)]]


def test_empty_trace_reads_nothing():
    red = trace_reduce.reduce({"devices": [], "host": {}})
    assert red["idle_share"] is None and red["device_ops"] == []
