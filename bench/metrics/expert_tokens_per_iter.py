"""Tokens a held expert computes per decode iteration in the window: the
program's count of the (token, k) routings of live slots that landed on the
experts held here (``decode.held_routings``, one count per scanned decode
call, summed over the MoE layers), over held experts times MoE layers times
the device iterations of the window's decode calls. In an expert-parallel
deployment a held expert sees tokens from every chip that routes to it; on
one chip it sees only this chip's batch, so this says how near the cut
comes to the deployment's load per expert. None where the program keeps no
such counter."""
from bench import program_spans as P

COUNTER = "decode.held_routings"


def read(run):
    obs = P.tracer()
    counters = getattr(obs, "counters", None)
    if counters is None:
        return None
    lo, hi = run.window
    routed = sum(c.n for c in counters()
                 if c.name == COUNTER and lo <= c.t <= hi)
    iters = sum(c[2] for c in run.decode_calls)
    conf = run.conf
    held = conf.get("n_routed_experts") or conf.get("num_experts", 0)
    moe_layers = conf["num_hidden_layers"] - conf.get("first_k_dense_replace", 0)
    if not (routed and iters and held and moe_layers):
        return None
    return routed / (held * moe_layers * iters)
