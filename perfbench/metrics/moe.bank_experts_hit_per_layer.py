"""Distinct experts of the WHOLE bank a decode step's tokens were routed
to, per layer (every layer is an expert layer): the sum of
``experts_hit`` over ``tdt.batcher.decode_round`` spans, over rounds and
layers. The step reads that many experts' weights."""
UNIT = "experts"


def read(run):
    kern = run.kernel("ring_moe_decode_step")
    return kern.experts_hit_per_layer(run) if kern.rounds(run) else None
