"""Host milliseconds a round that ``FedSim.run_round`` spends working:
the own time (duration less children) of every ``baton.round*`` span
but ``baton.round.sync``, where the host only waits for the waves. What
a round costs the host beside its wait: once the round no longer syncs
inside itself this is what has to fit under the previous round's wave,
whatever the device's idle reads."""

ROUND = "baton.round"
WAIT = "baton.round.sync"

LAYER = "round loop"
UNIT = "ms"
MOVES = "samples_per_s_per_chip"
SOURCE = "program_span"


def read(reduced, counters, cell):
    if reduced is None:
        return None
    own = [s for name, s in reduced["host_self_s"].items()
           if name != WAIT and (name == ROUND or name.startswith(ROUND + "."))]
    if not own:
        return None
    return 1e3 * sum(own) / reduced["n_rounds"]
