"""Median submit-to-result seconds of every job submitted in the window."""
from stats import latencies, percentile


def read(rec):
    return percentile(latencies(rec), 50)
