"""JAX compile accounting (DESIGN.md §15.4).

Two small instruments, all process-global (compilation caches are):

**Retracing counters.**  Every jitted hot-path kernel calls
``note_trace("<site>")`` as its first statement.  A jitted function body
only executes while JAX is *tracing* it — a cache hit dispatches the
compiled executable without touching Python — so the counter counts
exactly one increment per (re)trace per call-site.  This is the
measurement behind two serving claims: the §13 traced-scalar step masks
mean mixed rung budgets share one compilation, and steady-state serving
after warmup performs **zero** new tracings (the CI recompile-budget gate
asserts both).  ``tracing_snapshot()``/``new_tracings_since()`` implement
the gate's warmup/steady-state delta.

**FLOP accounting.**  ``pack_flops(metas)`` prices one megabatch pack:
every trial costs the group-maximal padded shape at the group-maximal
scan length, its useful work is its own shape at its own step budget —
the absolute-FLOPs companion of the scheduler's relative ``merge_waste``
ratio, built on ``launch/flops.py``'s analytic ``tabular_trial_flops``.
"""
from __future__ import annotations

import threading
from typing import Dict, Sequence

from .metrics import render_exposition_line

__all__ = ["new_tracings_since", "note_trace", "pack_flops",
           "render_prometheus", "reset_tracing", "total_tracings",
           "tracing_counts", "tracing_snapshot"]

_lock = threading.Lock()
_TRACE_COUNTS: Dict[str, int] = {}


# ---------------------------------------------------------------------------
# retracing counters
# ---------------------------------------------------------------------------


def note_trace(site: str) -> None:
    """Count one jit tracing of ``site``.

    Call as the first statement of a jitted function body: the body runs
    once per trace (compilation-cache miss) and never on a cached
    dispatch, so the count is exactly the number of compilations XLA was
    asked for at this call-site."""
    with _lock:
        _TRACE_COUNTS[site] = _TRACE_COUNTS.get(site, 0) + 1


def tracing_counts() -> Dict[str, int]:
    """Per-site tracing counts since process start (or ``reset_tracing``)."""
    with _lock:
        return dict(_TRACE_COUNTS)


def total_tracings() -> int:
    with _lock:
        return sum(_TRACE_COUNTS.values())


def tracing_snapshot() -> Dict[str, int]:
    """Alias of ``tracing_counts`` named for the warmup/steady-state
    protocol: snapshot after warmup, diff after steady-state traffic."""
    return tracing_counts()


def new_tracings_since(snapshot: Dict[str, int]) -> Dict[str, int]:
    """Per-site tracings that happened after ``snapshot`` was taken
    (empty dict == the recompile budget held)."""
    now = tracing_counts()
    delta = {site: n - snapshot.get(site, 0) for site, n in now.items()}
    return {site: n for site, n in delta.items() if n > 0}


def reset_tracing() -> None:
    with _lock:
        _TRACE_COUNTS.clear()


# ---------------------------------------------------------------------------
# megabatch FLOP accounting
# ---------------------------------------------------------------------------


def pack_flops(metas: Sequence) -> tuple:
    """``(padded_flops, useful_flops)`` of one megabatch pack.

    ``metas`` are the scheduler's ``CohortMeta`` entries: ``shape =
    (N_tr, N_val, d, n_classes)`` plus per-trial ``steps``.  Padded cost
    prices every trial at the group-maximal shape and scan length (what
    the fused dispatch actually executes); useful cost is each trial's
    own shape and budget (what a solo run would have needed)."""
    from ..launch.flops import tabular_trial_flops
    ntr = max(m.shape[0] for m in metas)
    nval = max(m.shape[1] for m in metas)
    d = max(m.shape[2] for m in metas)
    c = max(m.shape[3] for m in metas)
    smax = max(max(m.steps) for m in metas)
    n_trials = sum(len(m.steps) for m in metas)
    padded = n_trials * tabular_trial_flops(ntr, nval, d, c, smax)
    useful = sum(
        tabular_trial_flops(m.shape[0], m.shape[1], m.shape[2], m.shape[3], st)
        for m in metas for st in m.steps)
    return float(padded), float(useful)


# ---------------------------------------------------------------------------
# exposition
# ---------------------------------------------------------------------------


def render_prometheus() -> str:
    """Prometheus text block for the process-global jit-tracing counters —
    appended to the scheduler registry's exposition by ``/v1/metrics``."""
    with _lock:
        traces = sorted(_TRACE_COUNTS.items())
    lines = [
        "# HELP jax_jit_tracings_total jit tracings per instrumented "
        "call-site (1 per compilation-cache miss)",
        "# TYPE jax_jit_tracings_total counter",
    ]
    lines.extend(render_exposition_line("jax_jit_tracings_total",
                                        [("site", site)], float(n))
                 for site, n in traces)
    if not traces:
        lines.append(render_exposition_line(
            "jax_jit_tracings_total", [("site", "none")], 0.0))
    return "\n".join(lines) + "\n"
