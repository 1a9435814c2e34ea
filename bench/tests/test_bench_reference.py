"""The plain reference against the program, and its control, on the CPU at a
size a test run holds.  The reference must agree with the program; the
bfloat16 control must miss by more than the benchmark's limit."""
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

D1 = json.loads((BENCH / "configs" / "d1-airline.json").read_text())
SHAPE = dict(D1["table"], n_rows=4000)


@pytest.fixture(scope="module")
def table():
    return tables.job_tables(SHAPE, tables.table_seed(2**31 + 5, 1))


def _spec_dict(s):
    return {"preproc": s.preproc, "feature_frac": s.feature_frac,
            "family": s.family, "hp": [list(kv) for kv in s.hp]}


@pytest.mark.parametrize("seed, families", [
    (0, ["logreg", "mlp", "linear_svm", "gnb", "centroid"]),
    (3, ["mlp"]), (0, ["gnb"])])
def test_population_matches_program(seed, families):
    from repro.automl.engine import _sample_specs
    want = _sample_specs(np.random.default_rng(seed), 24, families)
    got = reference.population(np.random.default_rng(seed), 24, families)
    assert got == [reference.spec_key(_spec_dict(s)) for s in want]


def test_codes_match_program(table):
    from repro.core.measures import factorize
    X, y, _, _ = table
    assert np.array_equal(reference.factorize_codes(X, y),
                          np.asarray(factorize(X, y).codes))


def test_rung_sizes():
    assert reference.rung_sizes(24 + 9 + 4, 3, 0.34) == [24, 9, 4]
    assert reference.rung_sizes(6 + 3 + 2, 3, 0.34) == [6, 3, 2]
    with pytest.raises(ValueError):
        reference.rung_sizes(2, 3, 0.34)


@pytest.fixture(scope="module")
def searched(table):
    """The program's Gen-DST on the table, as a job would run it."""
    from repro.core.gen_dst import GenDSTConfig, gen_dst
    from repro.core.measures import factorize
    from repro.core.substrat import dst_feature_columns
    X, y, _, _ = table
    coded = factorize(X, y)
    cfg = GenDSTConfig(**dict(D1["plan"]["gen_dst"], psi=4, phi=16))
    res = gen_dst(jax.random.key(3), coded, n=63, m=6, cfg=cfg)
    cols = dst_feature_columns(np.asarray(res.col_mask), coded.target_col)
    return np.asarray(res.row_idx), cols, float(res.fitness)


def test_reference_fitness_matches_program(table, searched):
    X, y, _, _ = table
    rows, cols, fitness = searched
    codes = reference.factorize_codes(X, y)
    gap = abs(reference.subset_fitness(codes, rows, cols) - fitness)
    assert gap <= 1e-5


def test_control_fails_the_fitness(table, searched):
    X, y, _, _ = table
    rows, cols, fitness = searched
    codes = reference.factorize_codes(X, y)
    gap = abs(reference.subset_fitness(codes, rows, cols, "control") - fitness)
    assert gap > run.load_limits("d1.fresh")["fitness_gap"]


def _program_search(table, searched, backend):
    from repro.automl.engine import AutoMLConfig, automl_fit
    X, y, _, _ = table
    rows, cols, _ = searched
    cfg = dict(D1["plan"]["sub_automl"], n_trials=10, rungs=[8, 24])
    res = automl_fit(X[rows][:, cols], y[rows], config=AutoMLConfig(
        n_trials=cfg["n_trials"], rungs=tuple(cfg["rungs"]),
        keep_frac=cfg["keep_frac"], val_frac=cfg["val_frac"],
        seed=cfg["seed"], backend=backend))
    search = reference.Search(X[rows][:, cols], y[rows], cfg)
    want = reference._winner(search, _spec_dict(res.spec), cfg, "reference")
    served = {"params": reference.served_params(
        jax.tree.map(np.asarray, res.params))}
    return res, search, cfg, served, want


@pytest.mark.parametrize("backend", ["loop", "batched"])
def test_winner_matches_program(table, searched, backend):
    """The program's sub-AutoML winner, retrained by the reference."""
    _res, _search, _cfg, served, want = _program_search(table, searched,
                                                        backend)
    assert reference.prob_gap(served, want) <= 1e-5


def test_frozen_state_fails_the_winner(table, searched):
    # the fault that sets the upper reading of ``sub_prob_gap``; the control
    # stays under it there and fails ``fitness_gap`` instead
    res, search, cfg, _served, want = _program_search(table, searched,
                                                      "batched")
    got = reference._winner(search, _spec_dict(res.spec), cfg, "frozen")
    assert reference.prob_gap(got, want) > run.load_limits(
        "d1.fresh")["sub_prob_gap"]


def test_prob_gap():
    want = {"spec": ("none", 1.0, "logreg", ()),
            "params": {"w": np.zeros((2, 2)), "b": np.zeros(2)},
            "X_val": np.ones((3, 2))}
    assert reference.prob_gap(want, want) == 0.0
    # a constant added to every class's logit changes no probability
    same = {"params": {"w": np.ones((2, 2)), "b": np.full(2, 3.0)}}
    assert reference.prob_gap(same, want) == pytest.approx(0.0, abs=1e-7)
    # logits (0, ln 3) on every row: probabilities (1/4, 3/4), TV 1/4
    other = {"params": {"w": np.zeros((2, 2)), "b": np.log([1.0, 3.0])}}
    assert reference.prob_gap(other, want) == pytest.approx(0.25, rel=1e-6)
    assert reference.prob_gap({"params": {"w": np.zeros((2, 3)),
                                          "b": np.zeros(2)}}, want) == 1.0
