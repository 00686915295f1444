"""The routed experts' grouped products' share of their roofline: the
least time the chip could take for their required work of a round's
real tokens (``fedbench/flops/<config>.py``: ``expert_flops_per_round``
and ``expert_bytes_per_round``, the expected rows on the held experts;
at 512 rows an expert the operations bind) over the device time of the
ops under the ``expert_matmul`` scope in a round's waves. ``None``
where the configuration counts no expert or no op carried the scope."""

from fedbench.roofline import least_seconds
from fedbench.trace_reduce import wave_ms_under

LAYER = "kernels"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    wave_ms = wave_ms_under(reduced, part="expert_matmul")
    required = cell["required"]
    if not wave_ms or "expert_flops_per_round" not in required:
        return None
    least, _ = least_seconds(required["expert_flops_per_round"],
                             required["expert_bytes_per_round"],
                             cell["peaks"])
    round_s = 1e-3 * wave_ms * counters["n_waves"]
    return 100.0 * least / cell["chips"] / round_s
