"""Device milliseconds of one execution of the wave program in ops
under the ``full_core`` scope (the attention cores of the full layers
that stand beside windowed ones: the same flash kernels under the
causal rule alone), by the self time of its ops. Mean over the cell's
devices."""

from fedbench.trace_reduce import wave_ms_under

LAYER = "local training + model"
UNIT = "ms"
MOVES = "round_s"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    return wave_ms_under(reduced, part="full_core")
