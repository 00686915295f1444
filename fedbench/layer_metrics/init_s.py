"""Host seconds from building the model to the parameters and the
cohort being on the device: ``jax.jit(model.init)``, the cohort made
from the seed, staging (sharding over the mesh where there is one)."""

LAYER = "set-up"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "host_clock"


def read(reduced, counters, cell):
    return counters.get("init_s")
