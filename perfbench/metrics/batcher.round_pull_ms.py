"""Mean time a decode round spent in ``.pull``: the host waiting for the
device (the step's device time less what the dispatch overlapped)."""
from harness import spans as sp

UNIT = "ms"


def read(run):
    spans = sp.of(run)
    if not spans:
        return None
    return sp.mean_ms([s.child_ns(".pull") for s in spans.named(sp.ROUND)])
