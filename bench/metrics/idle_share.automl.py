"""Share of the traced window in which the device was idle while the
driver thread was in the AutoML engine (``automl.init``, ``automl.rung``,
``automl.finish``); see ``idle_spans.py``.  Nothing annotated, nothing
reported."""
import idle_spans


def read(rec):
    return idle_spans.share(rec, "automl")
