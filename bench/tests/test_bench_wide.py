"""The wide configuration (``amlb-fashion-mnist``) on the CPU, at a small
copy of its shape: 2,500 rows x 160 columns, more than one 128-lane tile,
10 classes, no categorical column.  One whole run of the cell
``fmnist.fresh`` with the program sound; its answers against the plain
reference; the bfloat16 control and the frozen fault, each put in the
program's place, against the cell's limits; and the ``variants`` span of
the AutoML engine's data-variant build."""
import copy
import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import reference  # noqa: E402
import run        # noqa: E402
import tables     # noqa: E402

CELL = "fmnist.fresh"
SEED = 2**33 + 17
SECONDS = 4.0


def _cpu(jax_mod, chips):
    d = jax_mod.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": 1}


@pytest.fixture(scope="module")
def wide(tmp_path_factory):
    """The configuration cut to a CPU's size and budgets (as
    ``test_bench_faults.py`` cuts D1's), with BENCHMARK.json's metrics.
    The noise shrinks with the informative columns (13 x sqrt(80 / 392)),
    so that the copy's classes separate about as well as the
    configuration's."""
    cfg = json.loads((BENCH / "configs" / "amlb-fashion-mnist.json")
                     .read_text())
    cfg["table"].update(n_rows=2500, n_cols=160, noise=5.9)
    cfg["plan"]["gen_dst"].update(psi=3, phi=8)
    cfg["plan"].update(dst_rows=45, dst_cols=40)
    cfg["plan"]["sub_automl"].update(n_trials=6, rungs=[4, 8])
    cfg["plan"]["fine_tune"].update(rungs=[4])
    cfg.update(check_jobs=2)
    path = tmp_path_factory.mktemp("cfg") / "wide.json"
    path.write_text(json.dumps(cfg))
    spec = copy.deepcopy(run.load_bench())
    spec["configs"] = [{"name": "wide", "file": str(path)}]
    spec["workloads"] = [{"name": CELL, "config": "wide", "traffic": "fresh",
                          "chips": 1}]
    return spec, cfg


@pytest.fixture(scope="module")
def ran(wide):
    """One run of the cell; the records its check read, and the check
    with the control and the fault in the program's place."""
    spec, cfg = wide
    seen = {}
    check = run.check_outputs

    def capture(rec, seed, mode="reference"):
        seen["rec"] = rec
        seen["modes"] = {m: check(rec, seed, m) for m in ("control", "frozen")}
        return check(rec, seed, mode)

    with pytest.MonkeyPatch.context() as mp:
        # CPU programs stay out of the checkout's compilation cache
        mp.setattr(run, "enable_cache", lambda jax_mod: None)
        mp.setattr(run, "check_outputs", capture)
        jax.clear_caches()
        try:
            res = run.run_cell(CELL, SEED, SECONDS, False, bench=spec,
                               require=_cpu)
        finally:
            jax.clear_caches()
    return res, seen["rec"], seen["modes"], cfg


def _correct(checks) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def test_wide_run_is_correct(ran):
    res, rec, _modes, _cfg = ran
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert {"job_p50_s", "jobs_per_min", "test_acc", "setup_s"} <= set(
        res["metrics"])
    assert rec.config["table"]["n_cols"] == 160


def test_reference_codes_match_program(ran):
    from repro.core.measures import factorize
    _res, rec, _modes, cfg = ran
    X, y, _, _ = tables.job_tables(cfg["table"], rec.jobs[0]["table"])
    assert X.shape == (2000, 160)
    assert np.array_equal(reference.factorize_codes(X, y),
                          np.asarray(factorize(X, y).codes))


@pytest.mark.parametrize("number, tol", [
    # the subset's fitness: the program's float32 entropies read 5.3e-05
    # from the float64 reference here (D1 on the chip: 3.2e-05, PERF.md)
    ("fitness_gap", 1e-4),
    ("sub_prob_gap", 1e-4),    # the sub-AutoML winner, retrained
    ("sub_trials_off", 0.0),   # every logged trial's accuracy
    ("ft_prob_gap", 1e-4),     # the fine-tune winner, retrained
])
def test_reference_agrees_with_program(ran, number, tol):
    """On the CPU both sides train in float32 at full precision, so they
    agree far inside the cell's limits."""
    res, _rec, _modes, _cfg = ran
    assert res["checks"][number]["value"] <= tol, res["checks"]
    assert res["checks"]["faults"]["value"] == 0


@pytest.mark.parametrize("mode", ["control", "frozen"])
def test_control_and_fault_are_not_correct(ran, mode):
    _res, _rec, modes, _cfg = ran
    assert not _correct(modes[mode]), modes[mode]


def test_variants_span_once_per_pass(ran):
    _res, rec, _modes, _cfg = ran
    jobs = [r for r in rec.setup_jobs + rec.jobs if r["status"] == "done"]
    assert jobs
    for r in jobs:
        spans = [s for s in r["spans"] if s["name"] == "variants"]
        assert sorted(s["attrs"]["phase"] for s in spans) == [
            "fine_tune", "sub_automl"], r["spans"]
        assert all(s["attrs"]["seconds"] > 0 for s in spans)
        assert sum(s["attrs"]["seconds"] for s in spans) < (
            r["phase_times"]["sub_automl"] + r["phase_times"]["fine_tune"])


def test_variants_metric_reads_the_spans(ran):
    _res, rec, _modes, _cfg = ran
    want = np.mean([sum(s["attrs"]["seconds"] for s in r["spans"]
                        if s["name"] == "variants")
                    for r in rec.jobs if r["status"] == "done"])
    assert run.reader("phase_s.variants")(rec) == pytest.approx(want)
    # a server that records no variant builds gives nothing
    old = copy.copy(rec)
    old.jobs = [dict(r, spans=[s for s in r["spans"]
                               if s["name"] != "variants"])
                for r in rec.jobs]
    assert run.reader("phase_s.variants")(old) is None
