"""Host time of ``DecodeEngine.step_chunk`` per device decode iteration, over
the window's decode calls, in ms."""


def read(run):
    iters = sum(c[2] for c in run.decode_calls)
    secs = sum(c[1] - c[0] for c in run.decode_calls)
    return 1e3 * secs / iters if iters else None
