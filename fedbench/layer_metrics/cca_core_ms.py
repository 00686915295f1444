"""Device milliseconds of one execution of the wave program in ops
under the ``cca_core`` scope (``models/transformer.py::cca_core``: the
attention kernels over 8 query heads on 2 key-value heads, forward,
made again and backward, with what XLA puts around them), by the self
time of its ops. Mean over the cell's devices. ``None`` where no op
carried the scope."""

from fedbench.trace_reduce import wave_ms_under

LAYER = "local training + model"
UNIT = "ms"
MOVES = "round_s"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    return wave_ms_under(reduced, part="cca_core")
