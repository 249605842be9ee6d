"""Mean host time of ``PrefillEngine.run`` per request sent in the window
(EMS fetch, ``prefill_continue`` chunks, block pack and store, first-token
read), in ms."""


def read(run):
    done = [r.prefill_s for r in run.reqs if r.first is not None]
    return 1e3 * sum(done) / len(done) if done else None
