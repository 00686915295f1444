"""Device milliseconds of one execution of the wave program in the
linear-attention mixers (``models/delta_rule.py::gated_delta_apply``:
projections with their adapters, convolutions, gates, output norm and
gate) with the recurrence they hold: ops whose innermost part is
``linear_attention`` or ``delta_scan``, by self time. Mean over the
cell's devices."""

from fedbench.trace_reduce import wave_ms_under

LAYER = "local training + model"
UNIT = "ms"
MOVES = "round_s"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    found = [ms for ms in (wave_ms_under(reduced, part=part)
                           for part in ("linear_attention", "delta_scan"))
             if ms is not None]
    return sum(found) if found else None
