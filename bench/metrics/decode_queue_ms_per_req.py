"""Mean host time a request of the window waited between its prefill and
handoff and the ``DecodePool.add`` that gave it a decode slot (the
program's ``queue.decode`` wait), in ms."""
from bench import program_spans as P


def read(run):
    return P.ms_per_request(run, ("queue.decode",))
