"""Programs lowered, then compiled or read from the compile cache, inside
the measured window (``jax.monitoring``)."""


def read(facts):
    return facts["compiles_in_window"]
