"""Server-side seconds decoding submit payloads
(``submit_decode_seconds_total``) per job of the window.  A server without
the counter reports nothing."""
from stats import counter_delta, done

COUNTER = "submit_decode_seconds_total"


def read(rec):
    n = len(done(rec))
    if not n or COUNTER not in rec.counters1:
        return None
    return counter_delta(rec, COUNTER) / n
