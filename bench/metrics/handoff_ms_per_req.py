"""Mean host time per request of the KV handoff: the transfer engine's
``transfer`` (fingerprint and delivery) and the decode pool's ``add``
(``cache_ops.insert_request`` into a slot), in ms."""


def read(run):
    done = [r.handoff_s for r in run.reqs if r.first is not None]
    return 1e3 * sum(done) / len(done) if done else None
