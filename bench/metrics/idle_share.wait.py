"""Share of the traced window in which the device was idle while the
driver thread waited (``drive.wait``: the admission grace, the wake wait,
the front end's lock); see ``idle_spans.py``.  Nothing annotated, nothing
reported."""
import idle_spans


def read(rec):
    return idle_spans.share(rec, "wait")
