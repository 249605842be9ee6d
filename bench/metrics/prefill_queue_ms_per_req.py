"""Mean host time a request of the window waited before its prefill started:
from ``ServingSystem.serve`` taking it in to ``PrefillEngine.run`` (the
program's ``queue.prefill`` wait), in ms."""
from bench import program_spans as P


def read(run):
    return P.ms_per_request(run, ("queue.prefill",))
