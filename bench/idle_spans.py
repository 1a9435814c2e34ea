"""The device's idle time of a trace, split by the layer the driver was in.

The served path writes profiler annotations (``obs/trace.py``) on the
thread that does the work.  The driver thread steps the scheduler: its
host line is the one that holds ``drive.step`` events.  ``split`` takes
the device-idle intervals of the traced window, the complement of the
union of device programs that ``trace_reduce.reduce`` measures
``idle_share`` by, and charges each idle nanosecond to the innermost
annotation of ``LAYER`` that covers it on the driver's line:

- ``wait``: ``drive.wait`` (admission grace, wake wait, the front end's
  lock);
- ``factorize``: ``factorize`` and the ``cache_probe`` inside it;
- ``gen_dst``: ``gen_dst``;
- ``automl``: ``automl.init``, ``automl.rung``, ``automl.finish``;
- ``unattributed``: ``drive.step`` outside any of these, and time outside
  every annotation.

The profiler keeps no annotation that was open when the trace began or
ended, so the work in progress at either edge would read as
``unattributed``.  It does keep the Python frames open there (its Python
tracer records them).  A frame that is, elsewhere on the line, the
innermost frame holding an annotation whole opens that annotation; before
the line's first ``drive.*`` annotation and after its last, such frames
stand in for the annotations the edges cut.

Each share is seconds over the traced window, averaged over the devices,
so the shares add up to ``idle_share``.  A trace with no ``drive.step``
events (a program without the annotations) gives nothing.
"""
from __future__ import annotations

import re

import trace_reduce

__all__ = ["LAYER", "LAYERS", "driver_line", "share", "split"]

LAYER = {"drive.wait": "wait", "factorize": "factorize",
         "cache_probe": "factorize", "gen_dst": "gen_dst",
         "automl.init": "automl", "automl.rung": "automl",
         "automl.finish": "automl", "drive.step": "unattributed"}
LAYERS = ("wait", "factorize", "gen_dst", "automl", "unattributed")

_FRAME = re.compile(r"\.py:\d+ ")   # the Python tracer's "file.py:12 name"
_memo: list = [None, None]   # [events, split(events)]: one trace per run


def driver_line(host: dict):
    """The host line that holds the most ``drive.step`` events, or None."""
    best, count = None, 0
    for name, events in host.items():
        n = sum(1 for e in events if e[0] == "drive.step")
        if n > count:
            best, count = name, n
    return best


def _openers(events) -> dict:
    """``{frame name: layer}``: the frames that, each time they hold an
    annotation whole as the innermost frame, hold one of a single layer."""
    votes, stack = {}, []   # stack: the open frames, innermost last
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if name in LAYER:
            for frame, end in reversed(stack):
                if end >= e:
                    votes.setdefault(frame, set()).add(LAYER[name])
                    break
        elif _FRAME.search(name):
            stack.append((name, e))
    return {f: layers.pop() for f, layers in votes.items()
            if len(layers) == 1}


def _spans(events):
    """``[(layer, t0, t1)]``: the line's annotations, and the frames that
    stand in for those cut at the trace's edges."""
    spans = [(LAYER[n], s, e) for n, s, e in events if n in LAYER]
    tops = [(s, e) for n, s, e in events if n in ("drive.wait", "drive.step")]
    head, tail = min(s for s, _e in tops), max(e for _s, e in tops)
    opens = _openers(events)
    for n, s, e in events:
        layer = opens.get(n)
        if layer is not None and s < head:
            spans.append((layer, s, min(e, head)))
        if layer is not None and e > tail:
            spans.append((layer, max(s, tail), e))
    return spans


def _labelled(spans, lo, hi):
    """``[(a, b, layer)]``: ``[lo, hi]`` cut where the innermost of the
    properly nested ``spans`` changes (layer None outside them)."""
    segs, stack, cur = [], [], lo
    for layer, s, e in sorted(spans, key=lambda sp: (sp[1], -sp[2])):
        s, e = max(s, lo), min(e, hi)
        while stack and stack[-1][0] <= s:
            end, label = stack.pop()
            if end > cur:
                segs.append((cur, end, label))
                cur = end
        if s > cur:
            segs.append((cur, s, stack[-1][1] if stack else None))
            cur = s
        if stack:
            e = min(e, stack[-1][0])
        if e > s:
            stack.append((e, layer))
    while stack:
        end, label = stack.pop()
        if end > cur:
            segs.append((cur, end, label))
            cur = end
    if hi > cur:
        segs.append((cur, hi, None))
    return segs


def _idle(device, lo, hi):
    """The intervals of ``[lo, hi]`` in which no program ran."""
    edges = [lo] + [x for ab in trace_reduce._union(device, lo, hi)
                    for x in ab] + [hi]
    return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def split(events: dict):
    """``{layer: share of the window}`` over ``LAYERS``, or None."""
    if _memo[0] is events:
        return _memo[1]
    line = driver_line(events["host"])
    out = None
    if line is not None:
        lo, hi = trace_reduce.window_of(events)
        segs = _labelled(_spans(events["host"][line]), lo, hi)
        devices = events["devices"] or [[]]
        ns = dict.fromkeys(LAYERS, 0)
        for dev in devices:
            i = 0
            for a, b in _idle(dev, lo, hi):
                while segs[i][1] <= a:
                    i += 1
                j = i
                while j < len(segs) and segs[j][0] < b:
                    s, e, label = segs[j]
                    ns[label or "unattributed"] += min(e, b) - max(s, a)
                    j += 1
        window = (hi - lo) * len(devices)
        out = {k: v / window for k, v in ns.items()} if window > 0 else None
    _memo[:] = [events, out]
    return out


def share(rec, layer: str):
    """The idle share of ``layer`` in the run's trace, or None."""
    if rec.trace is None:
        return None
    shares = split(rec.trace)
    return None if shares is None else shares[layer]
