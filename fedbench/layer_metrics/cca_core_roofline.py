"""The compressed attention core's share of its roofline: the least
time the chip could take for the core's required work of a round's real
tokens (``fedbench/flops/<config>.py``: ``cca_core_flops_per_round``
and ``cca_core_bytes_per_round``: the causal pairs alone, 8 query
heads of 128, keys and values read once a key-value head; the
operations bind) over the device time of the ops under the
``cca_core`` scope in a round's waves. ``None`` where the
configuration counts no such core or no op carried the scope."""

from fedbench.roofline import least_seconds
from fedbench.trace_reduce import wave_ms_under

LAYER = "kernels"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    wave_ms = wave_ms_under(reduced, part="cca_core")
    required = cell["required"]
    if not wave_ms or "cca_core_flops_per_round" not in required:
        return None
    least, _ = least_seconds(required["cca_core_flops_per_round"],
                             required["cca_core_bytes_per_round"],
                             cell["peaks"])
    round_s = 1e-3 * wave_ms * counters["n_waves"]
    return 100.0 * least / cell["chips"] / round_s
