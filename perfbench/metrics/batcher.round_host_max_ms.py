"""The worst decode round's host cost (``batcher.round_host_ms`` of one
round): a serving thread that stalled between the pull and the next
dispatch shows here, a device that stalled shows in the pull."""
from harness import spans as sp

UNIT = "ms"


def read(run):
    spans = sp.of(run)
    host = sp.round_host_ns(spans) if spans else []
    return max(host) / 1e6 if host else None
