"""Device time of one admission's program of the Mamba-2 / expert model
(one slot's bucket of rows through every layer: the projections, the
convolution, the chunked SSD kernel, the tiled attention, the expert
banks; the state's, ring's and pages' write), fullest device. The
arithmetic is ``step.prefill_device_ms``'s."""
from harness import cells

UNIT = "ms"


def read(run):
    return cells.load_module("metrics", "step.prefill_device_ms").read(run)
