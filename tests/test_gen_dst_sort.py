"""Gen-DST's sort path (DESIGN.md §16.3): entropies from one sort per
candidate column instead of a scatter-add histogram.

The histogram helpers stay the plain reference: every column's entropy must
match ``column_entropy_from_counts(column_counts(...))`` within 2e-6, and
bit for bit where a column takes at most two values.  The served program must hold
no scatter-add, and a config that carries counts must still carry them.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.automl.engine import AutoMLConfig
from repro.core.gen_dst import (
    GenDSTConfig, _gen_dst_jit, _population_entropy, gen_dst_path,
)
from repro.core.measures import (
    column_counts, column_entropy_from_counts, full_column_entropy,
)
from repro.core.substrat import SubStratConfig
from repro.service import Scheduler

B = 256


def _codes(rng, N, M, hi):
    return rng.integers(0, hi, (N, M)).astype(np.int32)


def _constant_columns(rng):
    c = _codes(rng, 300, 5, 2)
    c[:, 1] = 0
    c[:, 3] = 7
    return c


def _top_codes(rng):
    return rng.choice(np.array([B - 2, B - 1], np.int32), (400, 4))


# (codes maker, leading axes of the row sets, rows per candidate, bit-equal).
# Bit-equal where every column takes at most two values: the sum then adds
# the same two terms in any order; elsewhere only the order differs.
CASES = {
    "rows_not_a_power_of_two": (lambda r: _codes(r, 500, 7, 11), (6,), 37, False),
    "two_valued_columns": (lambda r: _codes(r, 500, 7, 2), (6,), 37, True),
    "constant_column": (_constant_columns, (4,), 50, True),
    "codes_at_B_minus_1": (_top_codes, (5,), 29, True),
    "all_256_codes": (lambda r: _codes(r, 900, 9, B), (3,), 237, False),
    "wider_than_128_columns": (lambda r: _codes(r, 300, 150, B), (3,), 24, False),
    "island_axes": (lambda r: _codes(r, 400, 6, 20), (2, 4), 16, False),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_population_entropy_matches_histogram(case):
    make, lead, n, exact = CASES[case]
    rng = np.random.default_rng(len(case))
    codes = make(rng)
    rows = rng.integers(0, codes.shape[0], lead + (n,)).astype(np.int32)
    got = np.asarray(_population_entropy(jnp.asarray(codes), jnp.asarray(rows)))
    ref = jax.jit(lambda c: column_entropy_from_counts(column_counts(c, B)))
    want = np.stack([np.asarray(ref(jnp.asarray(codes[r])))
                     for r in rows.reshape(-1, n)]).reshape(got.shape)
    assert got.shape == lead + (codes.shape[1],)
    if exact:
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)


def test_full_column_entropy_past_a_row_chunk():
    """F(D) over more than 65,536 rows, with 256-valued columns."""
    rng = np.random.default_rng(0)
    codes = np.concatenate(
        [_codes(rng, 70_001, 3, B), _codes(rng, 70_001, 2, 4)], axis=1)
    codes[:, 2] = B - 1
    got = np.asarray(full_column_entropy(jnp.asarray(codes), B))
    want = np.asarray(column_entropy_from_counts(
        column_counts(jnp.asarray(codes), B)))
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)
    assert got[2] == 0.0


# -- the mechanism: what the served program holds ---------------------------


def _lowered(cfg, N=64, M=6, n=8, Bw=16):
    codes = jnp.zeros((N, M), jnp.int32)
    return _gen_dst_jit.lower(jax.random.key(0), codes, codes.astype(jnp.float32),
                              n=n, m=3, cfg=cfg, B=Bw, target=M - 1).as_text()


def _scatter_bodies(text):
    """The update region of every scatter in a StableHLO module."""
    return [text[m.start():text.index("stablehlo.return", m.start())]
            for m in re.finditer(r'"stablehlo\.scatter"', text)]


def test_served_program_has_no_scatter_add():
    cfg = GenDSTConfig(psi=2, phi=4)            # jnp, entropy, cross_every 1
    assert gen_dst_path(cfg) == "sort"
    bodies = _scatter_bodies(_lowered(cfg))
    assert bodies                               # .at[].set stays allowed
    assert not [b for b in bodies if "stablehlo.add" in b]


def test_incremental_program_still_carries_counts():
    cfg = GenDSTConfig(psi=3, phi=4, cross_every=3, incremental=True)
    assert gen_dst_path(cfg) == "counts"
    text = _lowered(cfg)
    assert "tensor<1x4x6x16xf32>" in text       # (I, phi, M, B) counts
    assert [b for b in _scatter_bodies(text) if "stablehlo.add" in b]


@pytest.mark.parametrize("cfg, want", [
    (GenDSTConfig(), "sort"),
    (GenDSTConfig(num_islands=2), "sort"),
    (GenDSTConfig(cross_every=2), "counts"),
    (GenDSTConfig(cross_every=2, incremental=False), "counts"),
    (GenDSTConfig(backend="pallas"), "pallas"),
    (GenDSTConfig(backend="pallas_fused", cross_every=3), "pallas_fused"),
    (GenDSTConfig(measure="pnorm"), "values"),
])
def test_path_follows_the_config(cfg, want):
    assert gen_dst_path(cfg) == want


# -- the counter ----------------------------------------------------------


@pytest.mark.parametrize("backend, want", [("jnp", "sort"),
                                           ("pallas_fused", "pallas_fused")])
def test_served_job_counts_its_search(backend, want):
    rng = np.random.default_rng(1)
    y = rng.integers(0, 2, 300)
    X = np.column_stack([y + rng.normal(0, 1.0, 300) for _ in range(4)])
    cfg = SubStratConfig(
        gen=GenDSTConfig(psi=2, phi=4, backend=backend),
        sub_automl=AutoMLConfig(n_trials=2, rungs=(5,)),
        ft_automl=AutoMLConfig(n_trials=2, rungs=(5,)))
    sched = Scheduler()
    job = sched.jobs[sched.submit(X.astype(np.float32), y, config=cfg)]
    sched.run()
    assert job.phase == "done"
    assert sched.metrics.to_dict()["gen_dst_total"]["values"] == {want: 1.0}
    assert f'gen_dst_total{{path="{want}"}} 1' in sched.metrics.render()
