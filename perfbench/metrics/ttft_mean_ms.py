"""Mean of first token minus the time the request was DUE, over all
requests of the window; one that failed makes it infinite, and the run
reports none (such a run is not correct either)."""
from harness import stats

UNIT = "ms"


def read(run):
    return stats.mean(stats.ttft_ms(run.records))
