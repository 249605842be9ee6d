"""Share of the traced wave in which the device was idle while the host was
in one of the EMS spans of a prefill (fetch, insert, pack, store; each idle
stretch put down to the innermost program span covering it), in %.

It also adds to the run's breakdown what the same reduction gives
(``bench/program_spans.py``): ``idle_s_in_program`` (device idle seconds by
innermost program span), ``program_idle_gaps`` (the ten longest idle gaps of
``idle_gaps``, named by program span) and ``device_s_by_program`` (device
seconds by HLO module)."""
from bench import program_spans as P


def read(run):
    red = P.traced_wave(run)
    if red is None:
        return None
    run.trace.breakdown.update(
        {k: red[k] for k in ("idle_s_in_program", "program_idle_gaps",
                             "device_s_by_program")})
    idle = sum(red["idle_s_in_program"].get(k, 0.0) for k in P.EMS_SPANS)
    return 100.0 * idle / red["window_s"]
