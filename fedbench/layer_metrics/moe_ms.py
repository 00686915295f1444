"""Device milliseconds of one execution of the wave program in the
expert layers (``models/moe.py::moe_apply``: router, sort, gathers,
the weighted return of the rows, the shared expert) with the grouped
products they hold: ops whose innermost part is ``moe`` or
``expert_matmul``, by self time. Mean over the cell's devices."""

from fedbench.trace_reduce import wave_ms_under

LAYER = "local training + model"
UNIT = "ms"
MOVES = "round_s"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    found = [ms for ms in (wave_ms_under(reduced, part=part)
                           for part in ("moe", "expert_matmul"))
             if ms is not None]
    return sum(found) if found else None
