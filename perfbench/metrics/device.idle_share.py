"""Share of the traced window in which no operation ran on the fullest
device: 1 - union of its device-op intervals / window."""
from harness import trace as tr

UNIT = "%"


def read(run):
    w = (run.window[1] - run.window[0]) / 1e9
    return 100.0 * (1.0 - tr.busy_s(run.ops()) / w) if w > 0 else None
