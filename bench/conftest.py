"""pytest: the metric readers under ``metrics/`` are not tests, whatever
their file names (``metrics/test_acc.py`` reads the metric ``test_acc``)."""
collect_ignore = ["metrics"]
