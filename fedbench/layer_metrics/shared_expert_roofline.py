"""The shared experts' share of their roofline: the least time the chip
could take for their required work of a round's real tokens
(``fedbench/flops/<config>.py``: ``shared_expert_flops_per_round`` and
``shared_expert_bytes_per_round``, every token through the wide SwiGLU's
three products forward and for the gradient of its input, and their
adapters; the operations bind) over the device time of the ops under
the ``shared_expert`` scope in a round's waves. A checkpointed block
runs the forward products twice, and the second run is the program's
cost and not required work: two thirds is the most this share can read.
``None`` where the configuration counts no shared expert or no op
carried the scope."""

from fedbench.roofline import least_seconds
from fedbench.trace_reduce import wave_ms_under

LAYER = "kernels"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    wave_ms = wave_ms_under(reduced, part="shared_expert")
    required = cell["required"]
    if not wave_ms or "shared_expert_flops_per_round" not in required:
        return None
    least, _ = least_seconds(required["shared_expert_flops_per_round"],
                             required["shared_expert_bytes_per_round"],
                             cell["peaks"])
    round_s = 1e-3 * wave_ms * counters["n_waves"]
    return 100.0 * least / cell["chips"] / round_s
