"""Mean client-side seconds of ``POST /v1/submit``: wire encode, HTTP,
decode and admission, for every job submitted in the window."""
from stats import mean_of


def read(rec):
    return mean_of([r["t_submitted"] - r["t_submit"] for r in rec.jobs
                    if "t_submitted" in r])
