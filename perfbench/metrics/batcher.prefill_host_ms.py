"""Mean over admissions of ``tdt.batcher.admit_prefill`` less its
``.pull``: building and uploading the prompt, the dispatch, sampling the
first token and the slot's state."""
from harness import spans as sp

UNIT = "ms"


def read(run):
    spans = sp.of(run)
    if not spans:
        return None
    return sp.mean_ms([s.dur - s.child_ns(".pull")
                       for s in spans.named(sp.PREFILL)])
