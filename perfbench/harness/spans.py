"""The program's OWN spans, read from the host planes of a traced run.

``triton_dist_tpu.obs.span`` writes a ``jax.profiler.TraceAnnotation``
whenever a profiler session runs, so the serving loop's spans
(``tdt.engine.*``, ``tdt.batcher.*``; docs/observability.md lists them)
lie in the run's ``.xplane.pb`` on the device trace's own clock, each with
its counts as the event's stats. ``harness/trace.py`` keeps the device
events and the benchmark's wrappers; this module re-reads the HOST planes
of the same file, keeps the events named ``tdt.*`` with their stats and
nests them per thread by interval. Plain form, for the recorded fixture:

    [{"name": ..., "thread": ..., "start": ns, "dur": ns, "stats": {...}}, ...]

A program without such spans (a parent commit) gives ``None``, and every
reader built on this returns ``None``.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

from harness import cells, trace as tr

PREFIX = "tdt."
SERVE, STEP, INGEST, SLEEP = (PREFIX + "engine." + n
                              for n in ("serve", "step", "ingest", "sleep"))
ROUND, PREFILL = PREFIX + "batcher.decode_round", PREFIX + "batcher.admit_prefill"


class Span:
    __slots__ = ("name", "thread", "start", "end", "stats", "parent", "children")

    def __init__(self, name, thread, start, dur, stats):
        self.name, self.thread = name, thread
        self.start, self.end = int(start), int(start) + int(dur)
        self.stats = dict(stats)
        self.parent, self.children = None, []

    @property
    def dur(self) -> int:
        return self.end - self.start

    @property
    def self_ns(self) -> int:
        """Duration less what the children cover (children of one span
        come from one thread, so they do not overlap each other)."""
        return self.dur - sum(c.dur for c in self.children)

    def child_ns(self, suffix: str) -> int:
        """Time under the children named ``<this span's name><suffix>``."""
        want = self.name + suffix
        return sum(c.dur for c in self.children if c.name == want)

    def child_ns_under(self, prefix: str) -> int:
        """Time under the children whose names start with ``prefix``."""
        return sum(c.dur for c in self.children if c.name.startswith(prefix))

    def under(self, prefix: str) -> bool:
        """Whether any span below this one has a name starting so."""
        return any(c.name.startswith(prefix) or c.under(prefix)
                   for c in self.children)


class Spans:
    """Every ``tdt.*`` span of a run, nested, cut to the serve call."""

    def __init__(self, spans: list[Span]):
        by_thread: dict[str, list[Span]] = {}
        for s in spans:
            by_thread.setdefault(s.thread, []).append(s)
        for group in by_thread.values():
            # a parent starts no later and ends no earlier than its child
            group.sort(key=lambda s: (s.start, -s.end))
            open_: list[Span] = []
            for s in group:
                while open_ and open_[-1].end < s.end:
                    open_.pop()
                if open_:
                    s.parent = open_[-1]
                    open_[-1].children.append(s)
                open_.append(s)
        serves = [s for s in spans if s.name == SERVE]
        # the window's serve call is the last one (warm-up runs before the
        # profiler starts; a trace that caught one still ends with this)
        self.serve = max(serves, key=lambda s: s.end) if serves else None
        if self.serve is not None:
            # the serving loop runs on one thread: another thread's spans
            # are not this call's
            t0, t1, thread = self.serve.start, self.serve.end, self.serve.thread
            spans = [s for s in spans
                     if s.thread == thread and t0 <= s.start and s.end <= t1]
        self.all = sorted(spans, key=lambda s: s.start)

    def named(self, name: str) -> list[Span]:
        return [s for s in self.all if s.name == name]

    def events(self, name: str) -> tr.Events:
        """The spans of one name as the trace reader's ``Events``."""
        hit = self.named(name)
        return tr.Events([name], [0] * len(hit), [s.start for s in hit],
                         [s.dur for s in hit])

    def to_json(self, t0: int, t1: int) -> list[dict]:
        """The spans inside ``[t0, t1)`` in the plain form, times from t0."""
        return [{"name": s.name, "thread": s.thread, "start": s.start - t0,
                 "dur": s.dur, "stats": s.stats}
                for s in self.all if t0 <= s.start and s.end <= t1]

    @classmethod
    def from_json(cls, rows: list[dict]) -> "Spans | None":
        spans = [Span(**row) for row in rows if row["name"].startswith(PREFIX)]
        return cls(spans) if spans else None


def load_json(path: str) -> "Spans | None":
    with open(path) as f:
        return Spans.from_json(json.load(f))


def load_xplane(path: str) -> "Spans | None":
    """The ``tdt.*`` events of the host planes, with their stats."""
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            thread = f"{plane.name}/{line.name}"
            for e in line.events:
                if e.name.startswith(PREFIX):
                    rows.append({"name": e.name, "thread": thread,
                                 "start": e.start_ns, "dur": e.duration_ns,
                                 "stats": dict(e.stats)})
    return Spans.from_json(rows)


def of(run) -> "Spans | None":
    """The spans of a traced run, parsed once for all readers; ``None``
    where the run has no trace on disk or the program wrote no span."""
    if "tdt_spans" not in run.__dict__:
        run.tdt_spans = None
        trace_dir = os.path.join(cells.ROOT, ".perfbench", "trace", run.cell.name)
        try:
            path = tr.find_xplane(trace_dir)
        except FileNotFoundError:
            return None
        t0 = time.monotonic()
        run.tdt_spans = load_xplane(path)
        n = len(run.tdt_spans.all) if run.tdt_spans else 0
        print(f"[perfbench spans] {n} tdt.* spans read from the host planes "
              f"in {time.monotonic() - t0:.2f} s", file=sys.stderr, flush=True)
    return run.tdt_spans


# -- the arithmetic the readers share --------------------------------------

def mean_ms(values_ns: list) -> float | None:
    return float(np.mean(values_ns)) / 1e6 if len(values_ns) else None


def round_host_ns(spans: Spans) -> list[int]:
    """Per decode round, its duration less the pull: upload + dispatch +
    sample + bookkeeping, the host's serial share of the round."""
    return [s.dur - s.child_ns(".pull") for s in spans.named(ROUND)]


def ingest_late_ms(spans: Spans) -> float | None:
    ingests = spans.named(INGEST)
    n = sum(int(s.stats.get("n", 0)) for s in ingests)
    late = sum(int(s.stats.get("late_us_sum", 0)) for s in ingests)
    return late / n / 1e3 if n else None


def idle_split_s(busy: np.ndarray, spans: Spans) -> tuple[float, float, float]:
    """``(window, idle with work, idle under sleep)`` in seconds: the idle
    gaps of one device (``busy`` = union of its op intervals) inside the
    serve call, split by whether the gap's middle lies under a
    ``tdt.engine.sleep`` span (nothing due, nothing in flight)."""
    window = (spans.serve.start, spans.serve.end)
    gaps = tr.idle_gaps(busy, window, spans.events(SLEEP))
    asleep = sum(secs for name, secs in gaps if name == SLEEP)
    with_work = sum(secs for name, secs in gaps if name != SLEEP)
    return (window[1] - window[0]) / 1e9, with_work, asleep
