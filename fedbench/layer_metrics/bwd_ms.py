"""Device milliseconds of one execution of the wave program in the
backward pass (ops whose scope lies under JAX's ``transpose(``; a
``custom_vjp``'s backward counts here too), by the ops' self time.
Mean over the cell's devices."""

from fedbench.trace_reduce import wave_ms_under

LAYER = "local training + model"
UNIT = "ms"
MOVES = "round_s"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    return wave_ms_under(reduced, phase="backward")
