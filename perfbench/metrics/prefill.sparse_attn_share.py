"""Device time under ``tdt.attn`` inside the admissions' programs over
those programs' own device time, under the sparse latent plan: the
indexer's scores and selection and both tiled attentions against the
feed-forward GEMMs. The
arithmetic is ``prefill.attn_share``'s."""
from harness import cells

UNIT = "%"


def read(run):
    return cells.load_module("metrics", "prefill.attn_share").read(run)
