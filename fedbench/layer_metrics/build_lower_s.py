"""Host seconds JAX spent turning jaxprs into MLIR modules, whole
process, by the program's build ledger
(``/jax/core/compile/jaxpr_to_mlir_module_duration``): the recursion
whose frames fault on the interpreter's stack (PERF.md section 6,
PR 37) is inside it."""

from fedbench.build_split import total

LAYER = "set-up"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(reduced, counters, cell):
    return total(counters, "lower_s")
