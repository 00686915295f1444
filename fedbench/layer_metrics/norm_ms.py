"""Device milliseconds of one execution of the wave program in ops
under a ``norm`` scope (GroupNorm in ResNet: ``models/resnet.py::
_group_norm``, forward and its hand-written backward), by the self
time of its ops. Mean over the cell's devices."""

from fedbench.trace_reduce import wave_ms_under

LAYER = "local training + model"
UNIT = "ms"
MOVES = "round_s"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    return wave_ms_under(reduced, part="norm")
