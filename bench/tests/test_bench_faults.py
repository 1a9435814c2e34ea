"""Whole benchmark runs of a tiny cell on the CPU, with the program sound and
with it broken underneath: ``correct`` must come out true for the first and
false for each fault.  The chip check is the only part of a run skipped."""
import copy
import dataclasses
import json
import sys
from pathlib import Path

import jax
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import run  # noqa: E402

SECONDS = 4.0


def _cpu(jax_mod, chips):
    d = jax_mod.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": 1}


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """BENCHMARK.json's metrics, with the D1 cells on a table cut to 2,500
    rows and budgets a CPU runs in seconds."""
    tiny = json.loads((BENCH / "configs" / "d1-airline.json").read_text())
    tiny["table"]["n_rows"] = 2500
    tiny["plan"]["gen_dst"].update(psi=3, phi=8)
    tiny["plan"].update(dst_rows=45, dst_cols=6)
    tiny["plan"]["sub_automl"].update(n_trials=6, rungs=[4, 8])
    tiny["plan"]["fine_tune"].update(rungs=[4])
    tiny.update(check_jobs=2)
    path = tmp_path_factory.mktemp("cfg") / "tiny.json"
    path.write_text(json.dumps(tiny))
    spec = copy.deepcopy(run.load_bench())
    spec["configs"] = [{"name": "tiny", "file": str(path)}]
    spec["workloads"] = [{"name": name, "config": "tiny", "traffic": mix,
                          "chips": 1}
                         for name, mix in (("d1.fresh", "fresh"),
                                           ("d1.recurring", "recurring"))]
    return spec


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    # CPU programs stay out of the checkout's compilation cache
    monkeypatch.setattr(run, "enable_cache", lambda jax_mod: None)


def _run(bench, workload="d1.fresh", seed=2**31 + 99):
    jax.clear_caches()
    try:
        return run.run_cell(workload, seed, SECONDS, False, bench=bench,
                            require=_cpu)
    finally:
        jax.clear_caches()


@pytest.mark.parametrize("workload", ["d1.fresh", "d1.recurring"])
def test_sound_program_is_correct(bench, workload):
    res = _run(bench, workload)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert {"job_p50_s", "jobs_per_min", "test_acc", "setup_s"} <= set(
        res["metrics"])


def _altered_fitness(monkeypatch):
    from repro.service import scheduler
    real = scheduler.run_strategy

    def altered(*a, **kw):
        res = real(*a, **kw)
        return dataclasses.replace(res, fitness=float(res.fitness) + 0.01)

    monkeypatch.setattr(scheduler, "run_strategy", altered)


def _state_unchanged(monkeypatch):
    from repro.automl import batched, models

    def frozen(grad_fn, params0, lr, epochs, n_steps=None):
        return params0

    monkeypatch.setattr(models, "adam_train", frozen)
    monkeypatch.setattr(batched, "adam_train", frozen)


def _half_the_rows(monkeypatch):
    from repro.automl import engine
    from repro.service import scheduler
    real = engine.search_init

    def halved(*a, **kw):
        state = real(*a, **kw)
        ctx = state.ctx
        half = len(ctx["y_tr"]) // 2
        for k in ("X_tr", "y_tr", "y_tr_j"):
            ctx[k] = ctx[k][:half]
        return state

    monkeypatch.setattr(scheduler, "search_init", halved)


@pytest.mark.parametrize("fault", [_altered_fitness, _state_unchanged,
                                   _half_the_rows])
def test_broken_program_is_not_correct(bench, monkeypatch, fault):
    fault(monkeypatch)
    res = _run(bench)
    assert not res["correct"], res["checks"]
