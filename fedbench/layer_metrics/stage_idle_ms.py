"""Device idle milliseconds a round inside the ``baton.round.stage``
spans: the chip waiting while the host slices a wave's clients out of
the cohort, pads the wave and places it (only a wave staged with
nothing running before it costs idle time). Mean over the cell's
devices."""

from fedbench.trace_reduce import idle_ms_in

LAYER = "round loop"
UNIT = "ms"
MOVES = "samples_per_s_per_chip"
SOURCE = "program_span"


def read(reduced, counters, cell):
    return idle_ms_in(reduced, "baton.round.stage")
