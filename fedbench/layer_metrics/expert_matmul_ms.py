"""Device milliseconds of one execution of the wave program in ops
under the ``expert_matmul`` scope (``models/moe.py::grouped_matmul``:
the grouped products of the routed experts alone, forward, recomputed
and backward), by the self time of its ops. Mean over the cell's
devices."""

from fedbench.trace_reduce import wave_ms_under

LAYER = "local training + model"
UNIT = "ms"
MOVES = "round_s"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    return wave_ms_under(reduced, part="expert_matmul")
