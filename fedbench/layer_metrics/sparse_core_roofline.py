"""The sparse attention core's share of its roofline: the least time
the chip could take for the core's required work of a round's real
tokens (``fedbench/flops/<config>.py``: ``sparse_core_flops_per_round``
and ``sparse_core_bytes_per_round``: scores and values over the keys a
query chose alone, ``min(t + 1, index_topk)`` of them; the operations
bind) over the device time of the ops under the ``mla_core`` scope in a
round's waves. A kernel that visits every causal pair and masks can
read at most the chosen share (43.75 % at 8,192 and 2,048) of what a
dense count would give it. ``None`` where the configuration counts no
such core or no op carried the scope."""

from fedbench.roofline import least_seconds
from fedbench.trace_reduce import wave_ms_under

LAYER = "kernels"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    wave_ms = wave_ms_under(reduced, part="mla_core")
    required = cell["required"]
    if not wave_ms or "sparse_core_flops_per_round" not in required:
        return None
    least, _ = least_seconds(required["sparse_core_flops_per_round"],
                             required["sparse_core_bytes_per_round"],
                             cell["peaks"])
    round_s = 1e-3 * wave_ms * counters["n_waves"]
    return 100.0 * least / cell["chips"] / round_s
