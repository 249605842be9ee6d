"""Mean host time per request of the window in the EMS fetch inside
``PrefillEngine.run``: the prefix match and fetch (``prefill.ems_fetch``)
and the fetched blocks' insert into a fresh cache (``prefill.ems_insert``),
in ms."""
from bench import program_spans as P


def read(run):
    return P.ms_per_request(run, ("prefill.ems_fetch", "prefill.ems_insert"))
