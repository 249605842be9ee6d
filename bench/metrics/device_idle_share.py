"""Share of the traced wave in which no operation ran on the device (the
union of operation intervals, ``bench/trace_reduce.py``), in %."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
