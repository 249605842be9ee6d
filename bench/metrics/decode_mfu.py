"""The whole decode step's share of the chip's bf16 peak, in %: operations
of every decode iteration in the window (``bench/counts.py``) over the host
time of the decode calls that ran them."""


def read(run):
    ops = sum(c[3] for c in run.decode_calls)
    secs = sum(c[1] - c[0] for c in run.decode_calls)
    if ops <= 0 or secs <= 0:
        return None
    return 100.0 * ops / (secs * run.peak["bf16_flops_per_s"])
