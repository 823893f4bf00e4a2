"""Mean over decode rounds of ``tdt.batcher.decode_round`` less its
``.pull``: upload + dispatch + sample + the per-slot bookkeeping, the
serial host cost a host-free step would remove."""
from harness import spans as sp

UNIT = "ms"


def read(run):
    spans = sp.of(run)
    return sp.mean_ms(sp.round_host_ns(spans)) if spans else None
