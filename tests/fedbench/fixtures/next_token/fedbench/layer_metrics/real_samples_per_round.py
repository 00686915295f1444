"""The real samples of one round, as the harness counted them."""

LAYER = "round loop"
UNIT = "count"
MOVES = "samples_per_s_per_chip"
SOURCE = "program_counter"


def read(reduced, counters, cell):
    return counters.get("real_samples")
