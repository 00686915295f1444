"""The share of the sample slots a round computes that hold no real
sample: 1 - real samples / (waves x wave size x capacity). The harness
counts a round's real samples and the slots of its clients (clients x
capacity: a client's rows past its ``n_samples``); the clients that pad
a wave are the program's to count, the ``real`` and ``padded``
attributes of its ``baton.round.stage`` spans."""

LAYER = "round loop"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "program_counter"


def read(reduced, counters, cell):
    staged = (reduced or {}).get("span_attrs", {}).get("baton.round.stage")
    if not staged or not counters.get("sample_slots"):
        return None
    wave_fill = staged["real"] / (staged["real"] + staged["padded"])
    return 100.0 * (1.0 - wave_fill * counters["real_samples"]
                    / counters["sample_slots"])
