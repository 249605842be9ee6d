"""Live slots over batch slots in the window's decode iterations, in %: the
decode tokens the window's requests got (one a live slot-iteration, which
``DecodeEngine.live_slot_iters`` counts) over the decode batch times the
device iterations of the window's decode calls (``DecodeEngine.iters``)."""


def read(run):
    iters = sum(c[2] for c in run.decode_calls)
    live = sum(len(r.stamps) - 1 for r in run.reqs if r.first is not None)
    if not iters:
        return None
    return 100.0 * live / (run.conf["deployment"]["decode_batch"] * iters)
