"""Share of the traced window in which the device was idle while the
driver thread was in no phase: inside ``drive.step`` but outside the
phases' annotations, or outside every annotation; see ``idle_spans.py``.
Nothing annotated, nothing reported."""
import idle_spans


def read(rec):
    return idle_spans.share(rec, "unattributed")
