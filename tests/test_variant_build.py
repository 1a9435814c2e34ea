"""The AutoML engine's data-variant build: one ``variants`` span per AutoML
pass of a job, whose seconds are a part of the passes', and an
``automl.variants`` profiler annotation nested in the
rung's, which the benchmark's idle split (``bench/idle_spans.py``) leaves
out of its layers, so that every ``idle_share.*`` reads as before."""
import sys
from pathlib import Path

import numpy as np
import pytest

import jax

from repro.automl.engine import AutoMLConfig
from repro.core.plan import plan
from repro.service import SubStratServer

BENCH = Path(__file__).resolve().parents[1] / "bench"
sys.path.insert(0, str(BENCH))

import idle_spans     # noqa: E402
import trace_reduce   # noqa: E402

PLAN = plan("gen_dst", n=24, m=4,
            sub_automl=AutoMLConfig(n_trials=6, rungs=(2, 4)),
            ft_automl=AutoMLConfig(n_trials=3, rungs=(2,)),
            psi=4, phi=10)


def _table(seed=0, N=96, d=6, c=3):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(N, d)).astype(np.float32)
    return X, (np.arange(N) % c).astype(np.int64)


@pytest.mark.parametrize("megabatch", [True, False])
def test_one_variants_span_per_pass(megabatch):
    """Whether the rung runs in the standing megabatch or solo, a search
    builds its variants at its first rung only."""
    srv = SubStratServer(megabatch=megabatch)
    X, y = _table()
    jid = srv.submit(X, y, key=jax.random.key(1), plan=PLAN)
    srv.scheduler.run()
    job = srv.scheduler.jobs[jid]
    assert job.phase == "done"
    spans = [s for s in job.spans if s["name"] == "variants"]
    assert [s["attrs"]["phase"] for s in spans] == ["sub_automl",
                                                    "fine_tune"]
    assert all(s["attrs"]["seconds"] > 0 and s["t1"] >= s["t0"]
               for s in spans)
    # within the rung that built it: no ledger key of its own
    assert "variants" not in " ".join(job.times)
    rung0 = next(s for s in job.spans if s["name"] == "sub_automl/rung0")
    assert rung0["t0"] <= spans[0]["t0"] and spans[0]["t1"] <= rung0["t1"]
    pt = srv.poll(jid).phase_times
    assert "variants" not in pt
    assert sum(s["attrs"]["seconds"] for s in spans) < (
        pt["sub_automl"] + pt["fine_tune"])


def test_feature_ranking_is_float64():
    """A ``feature_frac`` below 1 keeps the columns of largest variance,
    ranked in float64: in float32, same-scale columns of nearly equal
    variance swap places (11 of these 200 positions on an x86 CPU), and
    the model's weight rows would come back in another order than a
    float64 ranking's."""
    from repro.automl.engine import _select_features
    rng = np.random.default_rng(1)
    X = rng.normal(size=(20000, 400)).astype(np.float32) * np.float32(13)
    want = np.argsort(-np.asarray(X, np.float64).var(axis=0))[:200]
    ctx = {"X_tr": X}
    np.testing.assert_array_equal(_select_features(0.5, ctx), want)
    # computed once per search, for every variant of it
    assert ctx["feature_var"].dtype == np.float64


def test_variants_annotation_nests_in_the_rung(tmp_path):
    srv = SubStratServer()
    X, y = _table(1)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0   # annotations only: a quicker job
    with jax.profiler.trace(str(tmp_path), profiler_options=options):
        jid = srv.submit(X, y, key=jax.random.key(2), plan=PLAN)
        srv.scheduler.run()
    assert srv.scheduler.jobs[jid].phase == "done"
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    events = [e for line in trace_reduce.load(path)["host"].values()
              for e in line]
    rungs = [e for e in events if e[0] == "automl.rung"]
    built = [e for e in events if e[0] == "automl.variants"]
    assert len(built) == 2
    for _n, s, e in built:
        assert any(a <= s and e <= b for _m, a, b in rungs)


MS = 1_000_000
BASE = {
    # busy [10,20] + [40,45]: idle [0,10], [20,40], [45,100]
    "devices": [[("jit_a", 10 * MS, 20 * MS), ("jit_b", 40 * MS, 45 * MS)]],
    "host": {"substrat-drive": [
        ("drive.wait", 0, 5 * MS),
        ("drive.step", 5 * MS, 90 * MS),
        ("factorize", 6 * MS, 30 * MS),
        ("gen_dst", 30 * MS, 50 * MS),
        ("automl.init", 50 * MS, 52 * MS),
        ("automl.rung", 52 * MS, 80 * MS),
        ("drive.wait", 92 * MS, 100 * MS)]},
}


@pytest.mark.parametrize("variants", [
    [(53, 70)],                      # inside the rung, over idle time
    [(50, 51), (55, 79)],            # inside automl.init, and the rung
    [(52, 80)],                      # the rung's whole extent
])
def test_variants_annotation_leaves_the_idle_split_alone(variants):
    with_v = {"devices": BASE["devices"], "host": {"substrat-drive": sorted(
        BASE["host"]["substrat-drive"]
        + [("automl.variants", a * MS, b * MS) for a, b in variants],
        key=lambda e: (e[1], -e[2]))}}
    want = idle_spans.split(dict(BASE))
    got = idle_spans.split(with_v)
    assert got == want
    assert got["automl"] == pytest.approx(0.30)   # [50,80] of 100 ms
    assert sum(got.values()) == pytest.approx(
        trace_reduce.reduce(with_v)["idle_share"])
