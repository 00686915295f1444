"""Device idle milliseconds inside the traced rounds, a round: what
the round loop's host work (slice, pad, stage, dispatch, fold, sync)
costs the chip. Mean over the cell's devices."""

from fedbench.trace_reduce import device_mean

LAYER = "round loop"
UNIT = "ms"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    if reduced is None:
        return None
    return 1e3 * device_mean(reduced, "idle_s") / reduced["n_rounds"]
