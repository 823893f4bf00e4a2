"""Device time under ``tdt.attn`` inside the admissions' programs over
those programs' own device time: what an admission spends on attention
(projections, scores-softmax-PV, the cache's write, out-projection)
against its feed-forward GEMMs, fullest device."""
from harness import scopes as sc

UNIT = "%"


def read(run):
    got = sc.inside(run, "prefill")
    if got is None:
        return None
    scopes, progs, ops = got
    return 100.0 * scopes.under(ops, "attn").total_s() / progs.total_s()
