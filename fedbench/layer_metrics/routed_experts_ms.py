"""Device milliseconds of one execution of the wave program in the
expert layers without their shared experts (``models/moe.py``: the
router, the sort of the assignments, the rows gathered from the tokens,
the grouped products, the SiLU and the backward's float32 passes, the
weighted return of the rows): ops whose innermost part is ``moe``,
``router`` or ``expert_matmul``, by self time, in a configuration that
lists ``shared_expert`` among its ``scopes.parts`` (elsewhere ``moe``
holds the shared expert too). Mean over the cell's devices. ``None``
where no op carried such a scope."""

from fedbench.trace_reduce import wave_ms_under

LAYER = "local training + model"
UNIT = "ms"
MOVES = "round_s"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    found = [ms for ms in (wave_ms_under(reduced, part=part)
                           for part in ("moe", "router", "expert_matmul"))
             if ms is not None]
    return sum(found) if found else None
