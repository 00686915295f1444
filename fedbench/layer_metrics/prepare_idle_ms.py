"""Device idle milliseconds a round inside ``baton.round.prepare`` and
its children: the chip waiting while the host splits the partition,
fetches ``n_samples``, sizes the waves, splits the round's key a client
(``baton.round.prepare.keys``) and, for a chosen cohort, takes its
clients out of the data (``baton.round.prepare.select``). Mean over the
cell's devices."""

from fedbench.trace_reduce import idle_ms_in

LAYER = "round loop"
UNIT = "ms"
MOVES = "samples_per_s_per_chip"
SOURCE = "program_span"


def read(reduced, counters, cell):
    return idle_ms_in(reduced, "baton.round.prepare",
                      "baton.round.prepare.keys",
                      "baton.round.prepare.select")
