"""Mean host time per request of the window in the EMS store inside
``PrefillEngine.run``: ``pack_blocks`` with its copy of every block to the
host (``prefill.ems_pack``, which also waits for the prefill programs to
finish) and ``EMSService.store`` (``prefill.ems_store``), in ms."""
from bench import program_spans as P


def read(run):
    return P.ms_per_request(run, ("prefill.ems_pack", "prefill.ems_store"))
