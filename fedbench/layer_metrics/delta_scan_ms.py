"""Device milliseconds of one execution of the wave program in ops
under the ``delta_scan`` scope (``models/delta_rule.py::
chunked_delta_rule``: the chunked recurrence of the linear-attention
layers alone, forward, recomputed and backward), by the self time of
its ops. Mean over the cell's devices."""

from fedbench.trace_reduce import wave_ms_under

LAYER = "local training + model"
UNIT = "ms"
MOVES = "round_s"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    return wave_ms_under(reduced, part="delta_scan")
