"""Device milliseconds of one execution of the wave program in ops
under the ``ssd_scan`` scope (``models/state_space.py::chunked_ssd``:
the chunked recurrence of the state-space branches alone, forward,
recomputed and backward), by the self time of its ops. Mean over the
cell's devices."""

from fedbench.trace_reduce import wave_ms_under

LAYER = "local training + model"
UNIT = "ms"
MOVES = "round_s"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    return wave_ms_under(reduced, part="ssd_scan")
