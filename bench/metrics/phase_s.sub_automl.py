"""Mean over the jobs submitted in the window of the server's
``phase_times["sub_automl"]`` (a merged dispatch charges each job an equal
share of its wall time)."""
from stats import done, mean_of


def read(rec):
    return mean_of([r["phase_times"]["sub_automl"] for r in done(rec)
                    if "phase_times" in r])
