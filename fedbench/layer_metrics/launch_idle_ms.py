"""Device idle milliseconds a round inside
``baton.round.dispatch.launch`` alone: from just before the jitted wave
program is called to the call's return, host side (argument handling,
the jit's fast path, the runtime's enqueue). A part of
``dispatch_idle_ms``; nothing where the program opens no such span.
Mean over the cell's devices."""

from fedbench.trace_reduce import idle_ms_in

LAYER = "round loop"
UNIT = "ms"
MOVES = "samples_per_s_per_chip"
SOURCE = "program_span"


def read(reduced, counters, cell):
    return idle_ms_in(reduced, "baton.round.dispatch.launch")
