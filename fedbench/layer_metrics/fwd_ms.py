"""Device milliseconds of one execution of the wave program in the
forward pass (ops whose scope lies under JAX's ``jvp(``), by the ops'
self time. Mean over the cell's devices."""

from fedbench.trace_reduce import wave_ms_under

LAYER = "local training + model"
UNIT = "ms"
MOVES = "round_s"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    return wave_ms_under(reduced, phase="forward")
