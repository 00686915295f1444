"""Device milliseconds of one execution of the wave program in the
attention cores of a model whose query heads stand 16 to a key-value
head, windowed and full together (``ops/flash_attention.py``'s forward
and backward kernels, their casts, the backward's ``delta`` and the sum
of the per-query-head ``dk`` and ``dv`` over a group): ops whose
innermost part is ``window_core`` or ``full_core``, by self time. Mean
over the cell's devices. ``None`` where no op carried such a scope."""

from fedbench.trace_reduce import wave_ms_under

LAYER = "local training + model"
UNIT = "ms"
MOVES = "round_s"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    found = [ms for ms in (wave_ms_under(reduced, part=part)
                           for part in ("window_core", "full_core"))
             if ms is not None]
    return sum(found) if found else None
