"""Device time under ``ffn/route`` per execution of the decode step where
a layer's routing is issued from the layer's input, before its attention
(router logits and top-k, the alignment; then the gather of sorted rows,
the weighted combine, the counters), fullest device. The arithmetic is
``step.moe_routing_ms``'s."""
from harness import cells

UNIT = "ms"


def read(run):
    return cells.load_module("metrics", "step.moe_routing_ms").read(run)
