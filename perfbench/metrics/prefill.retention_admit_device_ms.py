"""Device time of one admission's program of the power-retention model
(one slot's bucket of rows through every layer: the projections, the
chunked retention kernel, the MLPs; the state's write), fullest device. The
arithmetic is ``step.prefill_device_ms``'s."""
from harness import cells

UNIT = "ms"


def read(run):
    return cells.load_module("metrics", "step.prefill_device_ms").read(run)
