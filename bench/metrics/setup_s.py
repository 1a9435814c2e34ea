"""Set-up seconds: start of the harness to the opening of the window
(imports, server, warm-up jobs, compilation or cache loading)."""


def read(rec):
    return rec.setup_s
