"""Device idle milliseconds a round that are the harness's own: inside
``fedbench.round`` around ``run_round`` (the key's ``fold_in``), inside
``fedbench.sync`` (the host fetch of a loss) and between spans (the
``loss_history[-1]`` after a round). The idle no change to the program
can take back: ``idle_ms_per_round`` less this is the program's. Mean
over the cell's devices."""

from fedbench.trace_reduce import (BETWEEN, HARNESS_PREFIX, HARNESS_ROUND,
                                   idle_ms_in)

LAYER = "round loop"
UNIT = "ms"
MOVES = "samples_per_s_per_chip"
SOURCE = "program_span"


def read(reduced, counters, cell):
    return idle_ms_in(reduced, HARNESS_ROUND, HARNESS_PREFIX + "sync",
                      BETWEEN)
