"""Share of the tables factorized in the window that the device path coded:
the rise of ``factorize_total{path="device"}`` over the rise of every path.
Nothing factorized, or a server without the counter: nothing reported."""

COUNTER = "factorize_total"


def read(rec):
    def rise(path):
        def at(snap):
            return float(snap.get(COUNTER, {}).get("values", {}).get(path, 0.0))
        return at(rec.counters1) - at(rec.counters0)
    total = rise("device") + rise("host")
    return rise("device") / total if total > 0 else None
