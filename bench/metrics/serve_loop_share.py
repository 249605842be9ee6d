"""Share of the window the host spends in the serving loop itself: outside
every prefill, handoff and decode span (scheduling, admission, EMS
bookkeeping between the engine calls), in %."""


def read(run):
    t0, end = run.window
    return 100.0 * (1.0 - run.rec.busy_s(t0, end) / run.seconds)
