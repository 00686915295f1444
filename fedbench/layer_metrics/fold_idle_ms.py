"""Device idle milliseconds a round inside ``FedSim.run_round``'s
``baton.round.fold`` span: the chip waiting while the host divides the
weighted sums leaf by leaf (one tiny program a leaf; on a mesh each a
four-device program). What one jitted fold can give back. Mean over
the cell's devices."""

from fedbench.trace_reduce import idle_ms_in

LAYER = "aggregation"
UNIT = "ms"
MOVES = "samples_per_s_per_chip"
SOURCE = "program_span"


def read(reduced, counters, cell):
    return idle_ms_in(reduced, "baton.round.fold")
