"""The state-space recurrence's share of its roofline: the least time
the chip could take for the recurrence's required work of a round's
real tokens (``fedbench/flops/<config>.py``: ``ssd_scan_flops_per_round``
and ``ssd_scan_bytes_per_round``, the recurrence as its lines are
written with the state never leaving the chip; at a state of 256 x 128
a head the two bounds lie within a few percent of each other) over the
device time of the ops under the ``ssd_scan`` scope in a round's waves.
``None`` where the configuration counts no such recurrence or no op
carried the scope."""

from fedbench.roofline import least_seconds
from fedbench.trace_reduce import wave_ms_under

LAYER = "kernels"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    wave_ms = wave_ms_under(reduced, part="ssd_scan")
    required = cell["required"]
    if not wave_ms or "ssd_scan_flops_per_round" not in required:
        return None
    least, _ = least_seconds(required["ssd_scan_flops_per_round"],
                             required["ssd_scan_bytes_per_round"],
                             cell["peaks"])
    round_s = 1e-3 * wave_ms * counters["n_waves"]
    return 100.0 * least / cell["chips"] / round_s
