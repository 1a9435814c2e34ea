"""Mean held-out test accuracy of the jobs submitted in the window."""
from stats import done, mean_of


def read(rec):
    return mean_of([r["result"]["final"]["test_acc"] for r in done(rec)])
