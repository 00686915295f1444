"""Device milliseconds of one execution of the wave program in ops
under the ``cca_mix`` scope (``models/transformer.py::cca_apply``: the
two convolutions over the joined query and key latents, the q-k mean,
the value shift, the heads' unit length and temperature, the rotation;
bandwidth-bound but for the second convolution's product a head), by
the self time of its ops. Mean over the cell's devices. ``None`` where
no op carried the scope."""

from fedbench.trace_reduce import wave_ms_under

LAYER = "local training + model"
UNIT = "ms"
MOVES = "round_s"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    return wave_ms_under(reduced, part="cca_mix")
