"""Distinct HELD experts a decode step's tokens were routed to, per layer
(every layer of the plan has a bank; ``experts_hit`` counts the chip's
share of it only): the step reads that many experts' weights."""
UNIT = "experts"


def read(run):
    kern = run.kernel("ssd_expert_gemm")
    got = kern.rounds(run)
    if not got:
        return None
    return (sum(int(s.stats["experts_hit"]) for s in got) / len(got)
            / kern.expert_layers(run))
