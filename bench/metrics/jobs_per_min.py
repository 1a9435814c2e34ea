"""Jobs per minute: every job submitted in the window that finished, over
the time from the window's opening until the last of them finished, so
that the rate covers all the work and all the time."""
from stats import done


def read(rec):
    jobs = done(rec)
    if not jobs:
        return None
    return 60.0 * len(jobs) / (max(r["t_done"] for r in jobs) - rec.t0)
