"""How uneven a step's routing is over the HELD share: the largest count
of assignments on one held expert in any expert layer over the mean
count a held expert gets (``assignments`` counts the held experts' only,
``n_routed_experts`` the 32 held). The
arithmetic is ``moe.load_max_over_mean``'s."""
from harness import cells

UNIT = "ratio"


def read(run):
    return cells.load_module("metrics", "moe.load_max_over_mean").read(run)
