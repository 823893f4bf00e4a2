"""Mean wait from the time a request was due until the engine admitted it."""
from harness import stats

UNIT = "ms"


def read(run):
    return stats.mean([(r.t_admitted - r.t_due) * 1e3
                       for r in run.records if r.ok and r.t_admitted is not None])
