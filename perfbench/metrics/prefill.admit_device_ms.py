"""Device time of one admission's program (one slot's bucket of rows
through every layer), fullest device. The arithmetic is
``step.prefill_device_ms``'s; this one moves ``tokens_per_s`` in a cell
whose window is a backlog."""
from harness import cells

UNIT = "ms"


def read(run):
    return cells.load_module("metrics", "step.prefill_device_ms").read(run)
