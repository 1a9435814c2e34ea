"""JAX compile/dispatch accounting (DESIGN.md §15.4).

Three small instruments, all process-global (compilation caches are):

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

**Dispatch profile hook.**  Opt-in: ``set_dispatch_hook(fn)`` installs a
callable that receives ``(name, seconds, meta)`` after every scheduler
dispatch — the seam for wiring ``jax.profiler`` traces or external
telemetry to exactly the dispatches of interest without patching the
scheduler.  ``install_monitoring()`` additionally subscribes to
``jax.monitoring`` events so XLA's own compile events land in the same
exposition.
"""
from __future__ import annotations

import threading
from typing import Callable, Dict, Optional, Sequence

from .metrics import render_exposition_line

__all__ = ["dispatch_event", "install_monitoring", "new_tracings_since",
           "note_trace", "pack_flops", "render_prometheus", "reset_tracing",
           "set_dispatch_hook", "total_tracings", "tracing_counts",
           "tracing_snapshot"]

_lock = threading.Lock()
_TRACE_COUNTS: Dict[str, int] = {}
_XLA_EVENTS: Dict[str, int] = {}
_monitoring_installed = False
_dispatch_hook: Optional[Callable] = None


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
# jax.monitoring bridge
# ---------------------------------------------------------------------------


def _on_event(event: str, **_kw) -> None:
    with _lock:
        _XLA_EVENTS[event] = _XLA_EVENTS.get(event, 0) + 1


def install_monitoring() -> None:
    """Subscribe to ``jax.monitoring`` events once per process.

    Event names are jax-internal; the counters are exported verbatim
    under ``jax_monitoring_events_total{event=...}`` as corroborating
    evidence next to the first-class ``note_trace`` counters, never as
    the primary signal."""
    global _monitoring_installed
    if not _monitoring_installed:
        from jax import monitoring
        monitoring.register_event_listener(_on_event)
        _monitoring_installed = True


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
# per-dispatch profile hook (opt-in)
# ---------------------------------------------------------------------------


def set_dispatch_hook(fn: Optional[Callable]) -> None:
    """Install (or clear, with None) the per-dispatch profile callback:
    ``fn(name, seconds, meta)`` fires after every scheduler dispatch."""
    global _dispatch_hook
    _dispatch_hook = fn


def dispatch_event(name: str, seconds: float, **meta) -> None:
    """Report one finished dispatch to the opt-in hook (no-op otherwise)."""
    hook = _dispatch_hook
    if hook is not None:
        hook(name, seconds, meta)


# ---------------------------------------------------------------------------
# exposition
# ---------------------------------------------------------------------------


def render_prometheus() -> str:
    """Prometheus text block for the process-global jit/XLA counters —
    appended to the scheduler registry's exposition by ``/v1/metrics``."""
    with _lock:
        traces = sorted(_TRACE_COUNTS.items())
        events = sorted(_XLA_EVENTS.items())
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
    lines.append("# HELP jax_monitoring_events_total raw jax.monitoring "
                 "event counts (best-effort corroboration)")
    lines.append("# TYPE jax_monitoring_events_total counter")
    lines.extend(render_exposition_line("jax_monitoring_events_total",
                                        [("event", ev)], float(n))
                 for ev, n in events)
    return "\n".join(lines) + "\n"
