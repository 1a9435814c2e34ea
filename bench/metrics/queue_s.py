"""Mean seconds from ``submit`` returning to the start of the job's
``factorize`` span: the wait for the server's single driver thread."""
from stats import done, mean_of


def _wait(r):
    for span in r.get("spans", ()):
        if span["name"] == "factorize":
            return span["t0"] - r["wall_submitted"]
    return None


def read(rec):
    return mean_of([_wait(r) for r in done(rec)])
