"""Device milliseconds of one execution of the wave program in ops
under the ``router`` scope of a configuration that names it a part
(``models/moe.py::route_mlp``: the down-projection, the state carried
from the layer before, the RMSNorm, the three-layer GELU MLP, the
softmax over the experts and the skip, the choice; float32 at
``highest``), by the self time of its ops. Mean over the cell's
devices. ``None`` where no op's innermost part was ``router`` (a
configuration whose ``scopes`` do not list it reads its router as
``moe``)."""

from fedbench.trace_reduce import wave_ms_under

LAYER = "local training + model"
UNIT = "ms"
MOVES = "round_s"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    return wave_ms_under(reduced, part="router")
