"""Observability subsystem (DESIGN.md §15): tracing, metrics, JAX
compile accounting.  No dependencies beyond the stdlib and jax — the
serving tier imports this unconditionally.

- ``obs.trace``   — structured spans with deterministic ids, a contextvar
  current-span, cross-process propagation through the wire header, and
  ``jax.profiler`` annotations that put the spans on the device trace's
  clock.
- ``obs.metrics`` — counters/gauges/histograms with Prometheus text
  exposition and a bit-identical state round-trip for checkpoints.
- ``obs.jaxprof`` — jit-retracing counters per call-site and
  padded-vs-useful FLOP accounting for megabatch packs.
"""
from . import jaxprof, metrics, trace

__all__ = ["jaxprof", "metrics", "trace"]
