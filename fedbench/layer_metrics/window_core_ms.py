"""Device milliseconds of one execution of the wave program in ops
under the ``window_core`` scope (the windowed layers' attention cores
alone: ``ops/flash_attention.py``'s forward and backward kernels with a
window, their casts and the backward's ``delta``), by the self time of
its ops. Mean over the cell's devices."""

from fedbench.trace_reduce import wave_ms_under

LAYER = "local training + model"
UNIT = "ms"
MOVES = "round_s"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    return wave_ms_under(reduced, part="window_core")
