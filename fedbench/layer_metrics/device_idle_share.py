"""1 - (union of the device's busy intervals) / (traced span), in
percent. Mean over the cell's devices."""

from fedbench.trace_reduce import device_mean

LAYER = "device"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    if reduced is None:
        return None
    return 100.0 * device_mean(reduced, "idle_s") / reduced["window_s"]
