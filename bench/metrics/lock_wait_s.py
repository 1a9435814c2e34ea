"""Seconds HTTP handlers waited for the front end's lock
(``http_lock_wait_seconds_total``, every route) per job of the window.  A
server without the counter reports nothing."""
from stats import counter_delta, done

COUNTER = "http_lock_wait_seconds_total"


def read(rec):
    n = len(done(rec))
    if not n or COUNTER not in rec.counters1:
        return None
    return counter_delta(rec, COUNTER) / n
