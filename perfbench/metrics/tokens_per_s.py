"""All output tokens of the window's requests over the wall seconds from
the window's opening to the last counted token."""
from harness import stats

UNIT = "tokens/s"


def read(run):
    return stats.tokens_per_s(run.records, run.t_open)
