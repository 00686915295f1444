"""Device milliseconds of one execution of the wave program in the
local optimizer's update (the trainer's ``optimizer`` scope), by the
ops' self time. XLA may fuse a small update into the backward pass's
fusions; its time then counts there. Mean over the cell's devices."""

from fedbench.trace_reduce import wave_ms_under

LAYER = "local training + model"
UNIT = "ms"
MOVES = "round_s"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    return wave_ms_under(reduced, phase="optimizer")
