"""Seconds of tracing, lowering and XLA compilation or persistent-cache
loading in set-up, per set-up job: what serving a table whose programs the
process has not yet seen costs before it can run.  Set-up submits each
catalog table once, so this is the price of a new table's contents."""


def read(rec):
    if not rec.setup_jobs:
        return None
    return rec.setup_compile_s / len(rec.setup_jobs)
