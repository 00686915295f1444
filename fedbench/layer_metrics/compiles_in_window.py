"""Programs JAX built (compiled, or loaded from the persistent cache)
after warm-up, while rounds were measured or traced: a
``jax.monitoring`` listener on the backend-compile duration event
counts them. Must be 0; a recompile in the loop shows here first."""

LAYER = "round loop"
UNIT = "count"
MOVES = "samples_per_s_per_chip"
SOURCE = "program_counter"


def read(reduced, counters, cell):
    return counters.get("compiles_in_window")
