"""Seconds of tracing, lowering and XLA compilation or persistent-cache
loading inside the window (JAX's compile events); 0 when set-up left
nothing to compile."""


def read(rec):
    return rec.compile_s
