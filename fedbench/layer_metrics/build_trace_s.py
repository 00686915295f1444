"""Host seconds JAX spent in outermost jaxpr traces, whole process, by
the program's build ledger (``/jax/core/compile/jaxpr_trace_duration``
entered at depth 0): the wave program's, the probe's second trace of it,
the reference's, the fold's and every small eager program's."""

from fedbench.build_split import total

LAYER = "set-up"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(reduced, counters, cell):
    return total(counters, "trace_s")
