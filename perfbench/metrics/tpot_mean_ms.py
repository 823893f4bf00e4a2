"""Sum of (finished - first token) over sum of (tokens - 1), pooled over
all requests of the window."""
from harness import stats

UNIT = "ms"


def read(run):
    return stats.tpot_mean_ms(run.records)
