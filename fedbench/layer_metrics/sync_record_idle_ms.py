"""Device idle milliseconds a round inside ``baton.round.sync`` and
``baton.round.record``: the host noticing that the waves are done
(``block_until_ready`` on the loss sum) and then writing the compute
record (a host fetch of ``n_samples`` and ``memory_stats()``) before it
dispatches the fold. Mean over the cell's devices."""

from fedbench.trace_reduce import idle_ms_in

LAYER = "round loop"
UNIT = "ms"
MOVES = "samples_per_s_per_chip"
SOURCE = "program_span"


def read(reduced, counters, cell):
    return idle_ms_in(reduced, "baton.round.sync", "baton.round.record")
