"""The windowed attention cores' share of their roofline: the least
time the chip could take for their required work of a round's real
tokens (``fedbench/flops/<config>.py``: ``window_core_flops_per_round``
and ``window_core_bytes_per_round``, over the pairs of a query and a key
inside its window alone; the operations bind) over the device time of
the ops under the ``window_core`` scope in a round's waves. A tile the
kernel visits holds pairs outside the window too, which it computes and
masks: they are the kernel's cost and not required work. ``None`` where
the configuration counts no such core or no op carried the scope."""

from fedbench.roofline import least_seconds
from fedbench.trace_reduce import wave_ms_under

LAYER = "kernels"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    wave_ms = wave_ms_under(reduced, part="window_core")
    required = cell["required"]
    if not wave_ms or "window_core_flops_per_round" not in required:
        return None
    least, _ = least_seconds(required["window_core_flops_per_round"],
                             required["window_core_bytes_per_round"],
                             cell["peaks"])
    round_s = 1e-3 * wave_ms * counters["n_waves"]
    return 100.0 * least / cell["chips"] / round_s
