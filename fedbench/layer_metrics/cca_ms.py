"""Device milliseconds of one execution of the wave program in the
compressed-convolutional-attention mixers
(``models/transformer.py::cca_apply``: the five projections with their
adapters, the two convolutions, the q-k mean, the value shift, the
norms, the rotation) with the attention core they hold: ops whose
innermost part is ``compressed_attention``, ``cca_mix`` or
``cca_core``, by self time. Mean over the cell's devices. ``None``
where no op carried such a scope."""

from fedbench.trace_reduce import wave_ms_under

LAYER = "local training + model"
UNIT = "ms"
MOVES = "round_s"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    found = [ms for ms in (wave_ms_under(reduced, part=part)
                           for part in ("compressed_attention", "cca_mix",
                                        "cca_core"))
             if ms is not None]
    return sum(found) if found else None
