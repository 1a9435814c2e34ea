"""Share of the traced window in which the device was idle while the
driver thread ran the Gen-DST search, its host sync included
(``gen_dst``); see ``idle_spans.py``.  Nothing annotated, nothing
reported."""
import idle_spans


def read(rec):
    return idle_spans.share(rec, "gen_dst")
