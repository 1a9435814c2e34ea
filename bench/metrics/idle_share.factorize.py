"""Share of the traced window in which the device was idle while the
driver thread factorized a table (``factorize``, with the ``cache_probe``
inside it); see ``idle_spans.py``.  Nothing annotated, nothing reported."""
import idle_spans


def read(rec):
    return idle_spans.share(rec, "factorize")
