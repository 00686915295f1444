"""Device milliseconds of one execution of the wave program in the
windowed mixers (``models/llama.py::_sliding_apply``: the four
projections with their adapters, the rotation, and the core, the flash
kernels that skip by the window with their casts and ``delta``): ops
whose innermost part is ``sliding_attention`` or ``window_core``, by
self time. Mean over the cell's devices. ``None`` where no op carried
such a scope."""

from fedbench.trace_reduce import wave_ms_under

LAYER = "local training + model"
UNIT = "ms"
MOVES = "round_s"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    found = [ms for ms in (wave_ms_under(reduced, part=part)
                           for part in ("sliding_attention", "window_core"))
             if ms is not None]
    return sum(found) if found else None
