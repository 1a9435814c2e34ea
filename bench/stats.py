"""Arithmetic over a run's records, shared by the metric readers.

A record is one job as its tenant saw it (``loadgen.py``): ``t_submit``
before the submit call, ``t_submitted`` after it, ``t_done`` when the
result came back (``time.monotonic()`` seconds), ``status``, and, for a
finished job, the answer and the server's ``phase_times`` and ``spans``.
The window is ``[rec.t0, rec.t1]``.
"""
from __future__ import annotations

import statistics

__all__ = ["done", "latencies", "percentile", "mean_of", "counter_delta"]


def done(rec):
    """Every job submitted in the window that finished, including those
    that finished after it closed."""
    return [r for r in rec.jobs if r["status"] == "done"]


def latencies(rec):
    """Submit-to-result seconds of every job of ``done``."""
    return [r["t_done"] - r["t_submit"] for r in done(rec)]


def percentile(values, q: int):
    """The ``q``-th percentile (``statistics.quantiles``, inclusive)."""
    if not values:
        return None
    if len(values) == 1:
        return values[0]
    return statistics.quantiles(values, n=100, method="inclusive")[q - 1]


def mean_of(values):
    values = [v for v in values if v is not None]
    return sum(values) / len(values) if values else None


def counter_delta(rec, name: str) -> float:
    """A counter of the server's registry, summed over its label values:
    its rise from the window's opening until the last job of ``done``
    finished."""
    def total(snap):
        fam = snap.get(name)
        if fam is None:
            return 0.0
        if "value" in fam:
            return float(fam["value"])
        return float(sum(fam.get("values", {}).values()))
    return total(rec.counters1) - total(rec.counters0)
