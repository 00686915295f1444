"""Device milliseconds of one execution of the wave program in the
latent-attention mixers (``models/transformer.py::mla_apply``: the
projections with their adapters, the latent's and the heads' norms, the
rotation) with the attention core they hold: ops whose innermost part
is ``latent_attention`` or ``mla_core``, by self time. Mean over the
cell's devices."""

from fedbench.trace_reduce import wave_ms_under

LAYER = "local training + model"
UNIT = "ms"
MOVES = "round_s"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    found = [ms for ms in (wave_ms_under(reduced, part=part)
                           for part in ("latent_attention", "mla_core"))
             if ms is not None]
    return sum(found) if found else None
