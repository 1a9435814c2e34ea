"""1 - (union of the device's program intervals) / (traced window)."""
import trace_reduce


def read(rec):
    if rec.trace is None:
        return None
    return trace_reduce.reduce(rec.trace)["idle_share"]
