"""Device milliseconds of one execution of the wave program in ops
under the ``index_select`` scope (``models/transformer.py::select_keys``
and ``chosen_keys``: the choice of a query's ``index_topk`` best keys,
once a layer and step, and the mask made of it, forward and backward),
by the self time of its ops. Mean over the cell's devices. ``None``
where no op carried the scope."""

from fedbench.trace_reduce import wave_ms_under

LAYER = "local training + model"
UNIT = "ms"
MOVES = "round_s"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    return wave_ms_under(reduced, part="index_select")
