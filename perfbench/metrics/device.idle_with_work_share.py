"""Share of the serve call in which the fullest device ran nothing though
the engine was not asleep: its idle gaps whose middle is NOT under a
``tdt.engine.sleep`` span (nothing due, nothing in flight), over the
``tdt.engine.serve`` span. ``device.idle_share`` less the open loop's
unavoidable idle."""
from harness import spans as sp, trace as tr

UNIT = "%"


def read(run):
    spans = sp.of(run)
    if not spans or spans.serve is None:
        return None
    window_s, with_work_s, _ = sp.idle_split_s(
        tr.busy_intervals(run.ops()), spans)
    return 100.0 * with_work_s / window_s if window_s > 0 else None
