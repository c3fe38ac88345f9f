"""Executables built or loaded from the compile cache during the measured
window (a ``jax.monitoring`` count; each is also printed on stderr)."""


def read(ctx):
    return ctx["compiles_in_window"]
