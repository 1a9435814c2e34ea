"""Rung dispatches (``dispatches_total``, every mode) per job submitted in
the window."""
from stats import counter_delta, done


def read(rec):
    n = len(done(rec))
    return counter_delta(rec, "dispatches_total") / n if n else None
