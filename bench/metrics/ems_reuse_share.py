"""Prompt tokens served from the EMS cache, as a share of all prompt tokens
of the requests sent in the window (``RequestResult.reused_tokens``), in
%."""


def read(run):
    done = [r for r in run.reqs if r.first is not None]
    total = sum(r.prompt_len for r in done)
    return 100.0 * sum(r.reused for r in done) / total if total else None
