"""Median time to first token from the time a request was due."""
from harness import stats

UNIT = "ms"


def read(run):
    return stats.percentile(stats.ttft_ms(run.records), 50)
