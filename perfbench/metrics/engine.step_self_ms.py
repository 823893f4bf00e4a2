"""Mean over the steps that ran the batcher of ``tdt.engine.step`` less
its ``tdt.batcher.*`` children: everything the engine does in a step
around the batcher (its own admit, observe, alerts, overload, probe, and
the batcher's ``step`` outside its spans)."""
from harness import spans as sp

UNIT = "ms"


def read(run):
    spans = sp.of(run)
    if not spans:
        return None
    batcher = sp.PREFIX + "batcher."
    return sp.mean_ms([s.dur - s.child_ns_under(batcher)
                       for s in spans.named(sp.STEP) if s.under(batcher)])
