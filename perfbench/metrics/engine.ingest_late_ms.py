"""How late the program's own load generator saw an arrival: the serve
loop looks at its heap only between steps, so a request that falls due
inside a decode round or an admission is popped late. Sum of
``late_us_sum`` over the sum of ``n`` of the ``tdt.engine.ingest`` spans:
the part of ``engine.queue_wait_ms`` that is not waiting for a slot."""
from harness import spans as sp

UNIT = "ms"


def read(run):
    spans = sp.of(run)
    return sp.ingest_late_ms(spans) if spans else None
