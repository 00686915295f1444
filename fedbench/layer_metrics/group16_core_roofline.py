"""The attention cores' share of their roofline where 16 query heads
stand to a key-value head, windowed and full layers together: the least
time the chip could take for their required work of a round's real
tokens (``fedbench/flops/<config>.py``: ``window_core_*`` over the pairs
inside the window plus ``full_core_*`` over the causal pairs, and the
bytes of q, k, v, the output and their four gradients once each, a
key-value head's gradients once a key-value head; the operations bind)
over the device time of the ops under the ``window_core`` and
``full_core`` scopes in a round's waves. What the kernel writes a query
head and sums outside itself is its cost and not required work.
``None`` where the configuration counts neither core or no op carried
either scope."""

from fedbench.roofline import least_seconds
from fedbench.trace_reduce import wave_ms_under

LAYER = "kernels"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"
CORES = ("window_core", "full_core")


def read(reduced, counters, cell):
    found = [ms for ms in (wave_ms_under(reduced, part=part)
                           for part in CORES) if ms]
    required = cell["required"]
    if not found or any(f"{core}_flops_per_round" not in required
                        for core in CORES):
        return None
    least, _ = least_seconds(
        sum(required[f"{core}_flops_per_round"] for core in CORES),
        sum(required[f"{core}_bytes_per_round"] for core in CORES),
        cell["peaks"])
    round_s = 1e-3 * sum(found) * counters["n_waves"]
    return 100.0 * least / cell["chips"] / round_s
