"""Share of the traced window in which the fullest device runs only
communication: an XLA collective, or one of the program's kernels that
only moves data or waits (kernels/collectives.py names them). Device
operations run one at a time, so their time is exposed by construction;
what a fused ring kernel waits INSIDE itself cannot be seen from outside
and is not counted."""
from harness import trace as tr

UNIT = "%"


def read(run):
    w = (run.window[1] - run.window[0]) / 1e9
    comm = run.ops().matching(run.kernel("collectives").PATTERN)
    if w <= 0 or not len(comm):
        return None
    return 100.0 * tr.busy_s(comm) / w
