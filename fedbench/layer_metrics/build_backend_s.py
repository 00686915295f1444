"""Host seconds inside JAX's backend builds, whole process, by the
program's build ledger (``/jax/core/compile/backend_compile_duration``):
an XLA compile or the load of its result from the persistent cache, as
JAX times the two together."""

from fedbench.build_split import total

LAYER = "set-up"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(reduced, counters, cell):
    return total(counters, "backend_s")
