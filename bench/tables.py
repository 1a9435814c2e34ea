"""The benchmark's tables: synthetic copies of the SubStrat paper's datasets.

A copy, kept with the benchmark, of the generator the program ships in
``repro.data.tabular`` (``make_dataset`` / ``train_test_split``): the same
shapes as the paper's Table 2, class-conditional Gaussian columns and
class-correlated categorical columns.  It lives here so that no change to
the program can move the benchmark's inputs.  Nothing is downloaded: every
table is made from a 32-bit content seed, which ``table_seed`` derives from
the run's ``--seed`` and the table's place in the traffic.
"""
from __future__ import annotations

import numpy as np

__all__ = ["make_table", "split", "table_seed", "job_tables"]


def table_seed(run_seed: int, *tags: int) -> int:
    """A 32-bit content seed from the run seed (any size) and integer tags."""
    words = [run_seed & 0xFFFFFFFF, (run_seed >> 32) & 0xFFFFFFFF,
             *(int(t) & 0xFFFFFFFF for t in tags)]
    return int(np.random.SeedSequence(words).generate_state(1)[0])


def make_table(shape: dict, seed: int):
    """``(X, y)`` of the configured shape: ``n_rows`` x ``n_cols`` float32
    features, ``n_classes`` integer labels."""
    rng = np.random.default_rng(seed)
    N, M, C = int(shape["n_rows"]), int(shape["n_cols"]), int(shape["n_classes"])
    n_cat = int(round(shape["frac_categorical"] * M))
    n_info = max(1, int(round(shape["frac_informative"] * M)))
    info = np.zeros(M, dtype=bool)
    info[rng.permutation(M)[:n_info]] = True

    y = rng.integers(0, C, N)
    X = np.empty((N, M), dtype=np.float32)
    class_means = rng.normal(0.0, 2.0, (C, M))
    for j in range(M):
        if j < n_cat:
            k = int(rng.integers(2, 12))          # cardinality
            if info[j]:
                probs = rng.dirichlet(np.ones(k) * 0.6, C)
                u = rng.random(N)
                X[:, j] = (u[:, None] < probs.cumsum(axis=1)[y]).argmax(axis=1)
            else:
                X[:, j] = rng.integers(0, k, N)
        else:
            mu = class_means[y, j] if info[j] else 0.0
            X[:, j] = mu + rng.normal(0.0, shape["noise"], N)
    return X, y


def split(X, y, test_frac: float, seed: int = 0):
    """``(X_train, y_train, X_test, y_test)``: a seeded random hold-out."""
    perm = np.random.default_rng(seed).permutation(len(y))
    n_test = max(1, int(test_frac * len(y)))
    te, tr = perm[:n_test], perm[n_test:]
    return X[tr], y[tr], X[te], y[te]


def job_tables(shape: dict, seed: int):
    """The train/test split a job sends, for one content seed."""
    X, y = make_table(shape, seed)
    return split(X, y, shape["test_frac"])
