"""The decode step's share of its roofline in the traced wave, in %: the
least time of every decode iteration (the larger of its operations over the
chip's bf16 peak and its bytes over HBM bandwidth, ``bench/counts.py``;
weights it needs and the live tokens' KV at the configuration's dtype),
over the device time that fell inside the decode spans."""


def read(run):
    if run.trace is None:
        return None
    least = sum(c[4] for c in run.traced_calls)
    device_s = run.trace.device_s_in.get("decode", 0.0)
    if least <= 0 or device_s <= 0:
        return None
    return 100.0 * least / device_s
