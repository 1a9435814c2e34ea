"""Share of the Gen-DST searches in the window that scored their candidates
from one sort per column: the rise of ``gen_dst_total{path="sort"}`` over the
rise of every path.  No search ran, or a server without the counter:
nothing reported."""

COUNTER = "gen_dst_total"


def read(rec):
    def values(snap):
        return snap.get(COUNTER, {}).get("values", {})
    v0, v1 = values(rec.counters0), values(rec.counters1)

    def rise(path):
        return float(v1.get(path, 0.0)) - float(v0.get(path, 0.0))
    total = sum(rise(p) for p in set(v0) | set(v1))
    return rise("sort") / total if total > 0 else None
