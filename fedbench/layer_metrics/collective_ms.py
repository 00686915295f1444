"""Device milliseconds a round of the collective ops (the three
``psum`` of a mesh wave), read on the first device."""

LAYER = "aggregation"
UNIT = "ms"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    if reduced is None:
        return None
    device = reduced["devices"][sorted(reduced["devices"])[0]]
    if "collective" not in device["category_s"]:
        return None
    return 1e3 * device["category_s"]["collective"] / reduced["n_rounds"]
