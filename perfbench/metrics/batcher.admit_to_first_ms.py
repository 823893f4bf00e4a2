"""Mean time from admission to the first token: the admission's prefill
and whatever the batcher did before it."""
from harness import stats

UNIT = "ms"


def read(run):
    return stats.mean([(r.t_first - r.t_admitted) * 1e3
                       for r in run.records if r.ok and r.t_admitted is not None])
