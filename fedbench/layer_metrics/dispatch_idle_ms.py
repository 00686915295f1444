"""Device idle milliseconds a round inside ``FedSim.run_round``'s
``baton.round.dispatch`` spans and their children: the chip waiting
while the host binds a wave program's arguments, calls it
(``baton.round.dispatch.launch``), and launches the eager programs that
add the wave's sums to the round's
(``baton.round.dispatch.accumulate``). A program without the children
reads the same total from the one span. Mean over the cell's
devices."""

from fedbench.trace_reduce import idle_ms_in

LAYER = "round loop"
UNIT = "ms"
MOVES = "samples_per_s_per_chip"
SOURCE = "program_span"


def read(reduced, counters, cell):
    return idle_ms_in(reduced, "baton.round.dispatch",
                      "baton.round.dispatch.launch",
                      "baton.round.dispatch.accumulate")
