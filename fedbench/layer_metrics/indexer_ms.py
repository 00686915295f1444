"""Device milliseconds of one execution of the wave program in ops
under the ``indexer`` scope (``models/transformer.py::index_scores``:
the three index projections, the key's norm, the rotation and the
scores of every causal pair, forward and made again in the backward),
by the self time of its ops. Mean over the cell's devices. ``None``
where no op carried the scope (a program without an indexer)."""

from fedbench.trace_reduce import wave_ms_under

LAYER = "local training + model"
UNIT = "ms"
MOVES = "round_s"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    return wave_ms_under(reduced, part="indexer")
