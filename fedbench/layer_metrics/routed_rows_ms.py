"""Device milliseconds of one execution of the wave program in the
expert layers outside their router and their grouped products
(``models/moe.py``: the sort of the assignments, the rows gathered from
the tokens, the SiLU and the backward's elementwise float32 passes, the
weighted return of the rows to their tokens): ops whose innermost part
is ``moe`` alone, by self time. Mean over the cell's devices."""

from fedbench.trace_reduce import wave_ms_under

LAYER = "local training + model"
UNIT = "ms"
MOVES = "round_s"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    return wave_ms_under(reduced, part="moe")
