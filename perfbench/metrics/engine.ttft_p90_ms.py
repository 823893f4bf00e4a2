"""90th percentile (nearest rank) of first token minus the time the
request was DUE, over all requests of the window; one that failed counts
as beyond any limit."""
from harness import stats

UNIT = "ms"


def read(run):
    return stats.percentile(stats.ttft_ms(run.records), 90)
