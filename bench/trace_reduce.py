"""From a profiler trace to the device's busy time, its programs and gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
event lists; ``reduce`` turns them into numbers:

- ``busy_s``: the union of the intervals in which a program ran on the
  device (the ``XLA Modules`` line of each TPU plane; its ``XLA Ops`` line
  where a plane has no module line), clipped to the traced window and
  averaged over the devices;
- ``window_s``: the traced window, and ``idle_share`` = 1 - busy/window;
- ``device_ops``: seconds per program (XLA module name, its ``(id)`` suffix
  dropped), the largest first;
- ``idle_gaps``: the longest gaps in which no program ran, each named by
  what the dispatching host thread was doing: the innermost host event on
  that thread that covers at least half of the gap.

The dispatching thread is the host line of the Python threads (events
named ``file.py:123 name`` by the profiler's Python tracer), or, where the
trace has none, the line with the most JAX dispatch events (names that
contain ``jit`` or ``Execute``).  ``compact`` keeps only what
``reduce`` reads, so a small recorded trace can be checked into the tests.
"""
from __future__ import annotations

import re

__all__ = ["load", "reduce", "compact", "program_seconds"]

_SUFFIX = re.compile(r"\(\d+\)$")
_DISPATCH = re.compile(r"jit|Execute", re.IGNORECASE)
_PY_FRAME = re.compile(r"\.py:\d+ ")


def _clean(name: str) -> str:
    return _SUFFIX.sub("", name.lstrip("$")).strip()


def load(path) -> dict:
    """``{"devices": [[(name, t0_ns, t1_ns), ...], ...], "host": {line:
    [(name, t0_ns, t1_ns), ...]}}`` from an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    devices, host = [], {}
    for plane in data.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/device:TPU"):
            line = lines.get("XLA Modules") or lines.get("XLA Ops")
            if line is not None:
                devices.append([(_clean(e.name), e.start_ns,
                                 e.start_ns + e.duration_ns)
                                for e in line.events])
        elif plane.name.startswith("/host:CPU"):
            for name, line in lines.items():
                key = f"{name}#{id(line)}" if name in host else name
                host[key] = [(_clean(e.name), e.start_ns,
                              e.start_ns + e.duration_ns)
                             for e in line.events]
    return {"devices": devices, "host": host}


def _union(intervals, lo, hi):
    """Merged, clipped intervals."""
    out = []
    for a, b in sorted((max(a, lo), min(b, hi)) for _n, a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _dispatch_line(host: dict):
    """The host line of the Python threads (the most events named like a
    Python frame, ``file.py:123 name``), or else the one with the most JAX
    dispatch events."""
    for pattern in (_PY_FRAME, _DISPATCH):
        best, count = None, 0
        for name, events in host.items():
            n = sum(1 for e in events if pattern.search(e[0]))
            if n > count:
                best, count = name, n
        if best is not None:
            return best
    return None


def _name_gap(events, a, b) -> str:
    need = 0.5 * (b - a)
    best = None
    for name, s, e in events:
        if min(e, b) - max(s, a) >= need and (best is None
                                              or e - s < best[1]):
            best = (name, e - s)
    return _clean(best[0]) if best else "no host event"


def window_of(events: dict):
    """The traced window: the span of every event the trace holds."""
    starts, ends = [], []
    for dev in events["devices"]:
        starts += [s for _n, s, _e in dev]
        ends += [e for _n, _s, e in dev]
    for evs in events["host"].values():
        starts += [s for _n, s, _e in evs]
        ends += [e for _n, _s, e in evs]
    return (min(starts), max(ends)) if starts else (0, 0)


def program_seconds(events: dict, prefix: str, lo=None, hi=None):
    """``(seconds, count)`` of the device programs whose name starts with
    ``prefix`` and that lie wholly inside ``[lo, hi]`` (ns), averaged over
    the devices."""
    if lo is None:
        lo, hi = window_of(events)
    secs, count = 0.0, 0
    for dev in events["devices"]:
        for name, s, e in dev:
            if name.startswith(prefix) and s >= lo and e <= hi:
                secs += (e - s) * 1e-9
                count += 1
    n = max(len(events["devices"]), 1)
    return secs / n, count / n


def reduce(events: dict, top: int = 10) -> dict:
    lo, hi = window_of(events)
    window_s = (hi - lo) * 1e-9
    n_dev = max(len(events["devices"]), 1)
    busy_ns, gaps, per_op = 0, [], {}
    dispatch = events["host"].get(_dispatch_line(events["host"]), [])
    for dev in events["devices"]:
        merged = _union(dev, lo, hi)
        busy_ns += sum(b - a for a, b in merged)
        for name, s, e in dev:
            name = _clean(name)
            per_op[name] = per_op.get(name, 0.0) + (min(e, hi) - max(s, lo)) * 1e-9
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    gaps.sort(key=lambda g: g[0] - g[1])
    busy_s = busy_ns * 1e-9 / n_dev
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "device_ops": sorted(([k, v / n_dev] for k, v in per_op.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[_name_gap(dispatch, a, b), (b - a) * 1e-9]
                      for a, b in gaps[:top]],
    }


def compact(events: dict, max_host_events: int = 4000) -> dict:
    """The events ``reduce`` reads: the devices, and the dispatching host
    line cut to its ``max_host_events`` longest events."""
    line = _dispatch_line(events["host"])
    host = sorted(events["host"].get(line, []), key=lambda e: e[1] - e[2])
    return {"devices": events["devices"],
            "host": {line or "none": sorted(host[:max_host_events],
                                            key=lambda e: e[1])}}
