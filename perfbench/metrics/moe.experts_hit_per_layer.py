"""Distinct experts a decode step's tokens were routed to, per expert
layer: the sum of ``experts_hit`` over ``tdt.batcher.decode_round`` spans,
over rounds and expert layers. The step reads that many experts' weights."""
UNIT = "experts"


def read(run):
    rounds = run.kernel("expert_gemm").rounds(run)
    if not rounds:
        return None
    layers = run.kernel("expert_gemm").expert_layers(run)
    return sum(int(s.stats["experts_hit"]) for s in rounds) / len(rounds) / layers
