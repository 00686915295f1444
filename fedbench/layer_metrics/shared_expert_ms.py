"""Device milliseconds of one execution of the wave program in the
shared experts of the expert layers (``models/moe.py::_held_part``:
the four shared experts as one SwiGLU of their widths together, its
three products with their adapters, the SiLU and the ``1 / n_shared``
of their mean): ops whose innermost part is ``shared_expert``, by self
time, a checkpointed block's second forward with them. Mean over the
cell's devices. ``None`` where no op carried the scope (a configuration
that does not list it among its ``scopes.parts`` counts it under
``moe``)."""

from fedbench.trace_reduce import wave_ms_under

LAYER = "local training + model"
UNIT = "ms"
MOVES = "round_s"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    return wave_ms_under(reduced, part="shared_expert")
