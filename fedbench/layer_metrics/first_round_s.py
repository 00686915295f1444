"""Host seconds of the first ``run_round`` of the cell's own shapes,
to the host fetch of its loss: tracing, lowering, and the XLA compile
or the load from the persistent cache are inside it."""

LAYER = "set-up"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "host_clock"


def read(reduced, counters, cell):
    return counters.get("first_round_s")
