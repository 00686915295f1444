"""The indexer's share of its roofline: the least time the chip could
take for the indexer's required work of a round's real tokens
(``fedbench/flops/<config>.py``: ``indexer_flops_per_round`` and
``indexer_bytes_per_round``: its projections and ``index_n_heads x
index_head_dim`` a causal pair, one pass; the operations bind) over the
device time of the ops under the ``indexer`` scope in a round's waves,
which hold the scores made again in the backward. ``None`` where the
configuration counts no indexer or no op carried the scope."""

from fedbench.roofline import least_seconds
from fedbench.trace_reduce import wave_ms_under

LAYER = "kernels"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    wave_ms = wave_ms_under(reduced, part="indexer")
    required = cell["required"]
    if not wave_ms or "indexer_flops_per_round" not in required:
        return None
    least, _ = least_seconds(required["indexer_flops_per_round"],
                             required["indexer_bytes_per_round"],
                             cell["peaks"])
    round_s = 1e-3 * wave_ms * counters["n_waves"]
    return 100.0 * least / cell["chips"] / round_s
