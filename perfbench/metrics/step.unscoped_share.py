"""Device time of the ops inside the decode step whose scope names no
part, over the summed time of every op inside it: the instrument's own
health (a refactor that drops a scope shows here), and what the compiler
adds under no name (prefetches, copies), fullest device."""
from harness import scopes as sc

UNIT = "%"


def read(run):
    got = sc.inside(run, "decode_step")
    if got is None:
        return None
    scopes, _, ops = got
    return 100.0 * scopes.unscoped(ops).total_s() / ops.total_s()
