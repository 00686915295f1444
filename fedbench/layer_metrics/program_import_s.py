"""Host seconds the import of ``baton_tpu`` took in this process
(``baton_tpu.IMPORT_S``, stamped on the package's first and last line):
inside the harness's ``init_s``, and the part of a job's start that
only the program can shorten."""

LAYER = "set-up"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(reduced, counters, cell):
    if "init_s" not in counters:  # a rehearsal reports no time
        return None
    import baton_tpu

    return getattr(baton_tpu, "IMPORT_S", None)
