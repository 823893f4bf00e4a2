"""Tokens that decode steps yielded (every output token but a request's
first, which its prefill yields) over the executions of the decode-step
program counted in the trace: how many of the slots a step serves."""
UNIT = "tokens/step"


def read(run):
    steps = len(run.modules("decode_step"))
    if not steps:
        return None
    return sum(len(r.tokens) - 1 for r in run.records if r.ok) / steps
