"""Backend compiles inside the measured window, counted from
``jax.monitoring``: 0 when set-up warmed every shape the window meets."""


def reduce(bundle):
    return bundle["compiles"]
