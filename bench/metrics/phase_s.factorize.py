"""Mean over the jobs submitted in the window of the server's
``phase_times["factorize"]`` (a merged dispatch charges each job an equal
share of its wall time)."""
from stats import done, mean_of


def read(rec):
    return mean_of([r["phase_times"]["factorize"] for r in done(rec)
                    if "phase_times" in r])
