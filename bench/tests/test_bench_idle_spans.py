"""The served path's profiler annotations, and the idle split that reads
them (``idle_spans.py``): on a job served on the CPU under the profiler,
on a hand-made trace, and on a recorded v5e trace that has none."""
import gzip
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import idle_spans     # noqa: E402
import run            # noqa: E402
import trace_reduce   # noqa: E402

SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
IDLE_METRICS = [m["name"] for m in SPEC["per_layer"]
                if m["name"].startswith("idle_share.")]
RECORDED = BENCH / "tests" / "data" / "trace_d1_fresh.json.gz"
JOB_LAYERS = ("factorize", "gen_dst", "automl.init", "automl.rung")


def test_served_job_annotations_reach_the_profiler(tmp_path):
    import jax
    from repro.automl.engine import AutoMLConfig
    from repro.core.plan import plan
    from repro.service import (SubStratHTTPClient, SubStratHTTPServer,
                               SubStratServer)

    p = plan("gen_dst", n=24, m=4,
             sub_automl=AutoMLConfig(n_trials=4, rungs=(2, 4)),
             ft_automl=AutoMLConfig(n_trials=2, rungs=(2,)), psi=4, phi=10)
    rng = np.random.default_rng(0)
    X = rng.normal(size=(48, 6)).astype(np.float32)
    y = (np.arange(48) % 3).astype(np.int64)
    http = SubStratHTTPServer(SubStratServer()).start()
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0   # annotations only: a quicker job
        with jax.profiler.trace(str(tmp_path), profiler_options=options):
            client = SubStratHTTPClient(http.url)
            jid = client.submit(X, y, key=jax.random.key(1), plan=p)
            client.result(jid, timeout_s=300)
    finally:
        http.close()
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    # the handlers' short-lived threads share one line name, and ``load``
    # keeps one line of each name: read theirs from the profile itself
    handlers = {e.name for plane in jax.profiler.ProfileData.from_file(
                    str(path)).planes if plane.name.startswith("/host:CPU")
                for line in plane.lines for e in line.events}
    assert {"http.decode", "http.lock"} <= handlers
    host = trace_reduce.load(path)["host"]
    names = {e[0] for events in host.values() for e in events}
    assert {"drive.wait", "drive.step", *JOB_LAYERS} <= names
    line = idle_spans.driver_line(host)
    assert line == "substrat-drive"
    steps = [e for e in host[line] if e[0] == "drive.step"]
    for layer in JOB_LAYERS:
        events = [e for e in host[line] if e[0] == layer]
        assert events, layer
        for _n, s, e in events:
            assert any(a <= s and e <= b for _m, a, b in steps), layer


def test_idle_split_on_a_hand_made_trace():
    ms = 1_000_000
    events = {
        # busy [10,20] + [40,45]: idle [0,10], [20,40], [45,100]
        "devices": [[("jit_a", 10 * ms, 20 * ms), ("jit_b", 40 * ms, 45 * ms)]],
        "host": {
            "python3": [("drive.wait", 0, 5 * ms),
                        ("drive.step", 5 * ms, 90 * ms),
                        ("factorize", 6 * ms, 30 * ms),
                        ("cache_probe", 25 * ms, 28 * ms),
                        ("gen_dst", 30 * ms, 50 * ms),
                        ("automl.rung", 52 * ms, 80 * ms),
                        ("$scheduler.py:1 step", 52 * ms, 60 * ms),
                        ("drive.wait", 92 * ms, 100 * ms)],
            "python3#2": [("http.lock", 0, 100 * ms)],
        },
    }
    split = idle_spans.split(events)
    assert split == pytest.approx({
        "wait": 0.13,           # [0,5] + [92,100]
        "factorize": 0.14,      # [6,10] + [20,30], cache_probe included
        "gen_dst": 0.15,        # [30,40] + [45,50]
        "automl": 0.28,         # [52,80]
        "unattributed": 0.15,   # drive.step's own [5,6] [50,52] [80,90],
    })                          # and [90,92] outside every annotation
    assert sum(split.values()) == pytest.approx(
        trace_reduce.reduce(events)["idle_share"])


def test_idle_split_fills_the_edges_from_the_opening_frames():
    """The trace cut an open ``drive.step`` at each edge, and the
    ``factorize`` inside it: the frames that open those annotations in the
    rest of the trace stand in for them there."""
    ms = 1_000_000
    step, fact = "$scheduler.py:5 step", "$scheduler.py:10 _factorize"
    events = {
        "devices": [[("jit_a", 40 * ms, 50 * ms)]],
        "host": {"substrat-drive": [
            (step, 0, 25 * ms), (fact, 0, 20 * ms),            # cut at the head
            ("drive.wait", 25 * ms, 30 * ms),
            (step, 29 * ms, 71 * ms), ("drive.step", 30 * ms, 70 * ms),
            (fact, 31 * ms, 45 * ms), ("factorize", 32 * ms, 44 * ms),
            ("drive.wait", 71 * ms, 80 * ms),
            (step, 80 * ms, 100 * ms), (fact, 81 * ms, 100 * ms),  # the tail
        ]},
    }
    split = idle_spans.split(events)
    assert split == pytest.approx({
        "wait": 0.14,           # [25,30] + [71,80]
        "factorize": 0.47,      # [0,20] + [32,40] + [81,100]
        "gen_dst": 0.0,
        "automl": 0.0,
        "unattributed": 0.29,   # step's own [20,25] [30,32] [50,70] [80,81],
    })                          # and [70,71] outside every annotation
    assert sum(split.values()) == pytest.approx(
        trace_reduce.reduce(events)["idle_share"])


def test_recorded_trace_reports_no_idle_split():
    events = json.loads(gzip.decompress(RECORDED.read_bytes()))
    rec = SimpleNamespace(trace=events)
    assert len(IDLE_METRICS) == 5
    for name in IDLE_METRICS:
        assert run.reader(name)(rec) is None, name
