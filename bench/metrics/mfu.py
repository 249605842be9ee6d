"""Model operations completed in the window over the window times the
chips' bf16 peak, in %: prompt tokens computed (reused tokens cost nothing)
for the requests whose prefill ended in the window, and every decode
iteration whose call ended in it (``bench/counts.py``)."""


def read(run):
    t0, end = run.window
    ops = sum(run.counts.prefill_flops(run.conf, r.reused, r.prompt_len)
              for r in run.reqs
              if r.first is not None and t0 <= r.first <= end)
    ops += sum(c[3] for c in run.decode_calls if c[1] <= end)
    if ops <= 0:
        return None
    return 100.0 * ops / (run.seconds * run.chips
                          * run.peak["bf16_flops_per_s"])
