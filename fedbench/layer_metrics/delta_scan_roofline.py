"""The chunked recurrence's share of its roofline: the least time the
chip could take for the recurrence's required work of a round's real
tokens (``fedbench/flops/<config>.py``: ``scan_flops_per_round`` and
``scan_bytes_per_round``; at heads of 96 x 192 the bytes bind, the
state never leaving the chip) over the device time of the ops under
the ``delta_scan`` scope in a round's waves. ``None`` where the
configuration counts no recurrence or no op carried the scope."""

from fedbench.roofline import least_seconds
from fedbench.trace_reduce import wave_ms_under

LAYER = "kernels"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    wave_ms = wave_ms_under(reduced, part="delta_scan")
    required = cell["required"]
    if not wave_ms or "scan_flops_per_round" not in required:
        return None
    least, _ = least_seconds(required["scan_flops_per_round"],
                             required["scan_bytes_per_round"], cell["peaks"])
    round_s = 1e-3 * wave_ms * counters["n_waves"]
    return 100.0 * least / cell["chips"] / round_s
