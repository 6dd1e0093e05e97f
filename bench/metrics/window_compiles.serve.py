"""XLA compiles plus persistent-cache loads inside the measured window,
counted by a ``jax.monitoring`` listener: every program the window calls
should have been built at set-up."""


def read(rec):
    n = rec.get("window_compiles")
    return None if rec.get("kind") != "serve" or n is None else n
