"""Host-side structured span tracer (ISSUE 9, the host half of the obs
layer).

Design rules, shared with ``resilience/health.py``:

- **Bounded** — a ring buffer of finished spans plus per-name streaming
  duration histograms behind one lock. The ring bound is
  ``ObsConfig.max_spans``; evictions are COUNTED and surfaced
  (``dropped_spans`` — no silent caps), and the per-name stats are
  streaming, so percentiles survive any number of evictions.
- **Deterministic** — every timestamp comes from the injectable
  resilience clock (``resilience/retry.py``), so
  ``retry.clock_scope(FakeClock())`` makes whole traces — and their
  chrome-JSON exports — byte-identical run to run (asserted in
  tests/test_obs.py). Spans recorded with explicit timestamps
  (:func:`record_span` — the serving engine's lifecycle phases, measured
  on the engine's own injectable clock) never read any clock here.
- **Zero overhead disarmed** — every entry point checks
  ``config.obs`` first; ``None`` (the default) traces nothing and adds
  one attribute read per call site.
- **One switch for the device trace** — :func:`span` is also a
  ``jax.profiler.TraceAnnotation`` whenever a profiler session runs
  (``jax.profiler.start_trace``, ``utils.group_profile``, a profiler
  server), whatever ``config.obs`` says: the span then lies in the host
  plane of the ``.xplane.pb``, on the same clock as the device ops, with
  its attributes as the event's stats. No session: one read of the
  profiler's own flag. The serving loop's ``tdt.*`` spans
  (docs/observability.md, "Spans in the device trace") come this way.

Nesting is tracked per thread: :func:`span` is a context manager whose
depth places it under its parent in the exported timeline, and
:func:`annotate` attaches attributes to the innermost OPEN span (how the
retry layer stamps its attempt counts onto the enclosing op span without
holding a handle).
"""

from __future__ import annotations

import dataclasses
import math
import threading
from typing import Any

from jax.profiler import TraceAnnotation as _TraceAnnotation

from triton_dist_tpu import config as _tdt_config
from triton_dist_tpu.resilience import retry as _retry

# True while a profiler session records host events (one atomic read)
_profiling = _TraceAnnotation.is_enabled

# --- minimal streaming log-binned histogram (ms) ---------------------------
# Self-contained on purpose: serving/metrics.py has a richer twin, but
# importing it would pull the serving package (engine -> models -> jax)
# into every obs consumer and create an import cycle (engine uses obs).

_HIST_LO, _HIST_HI, _BINS_PER_DECADE = 1e-4, 1e7, 8
_N_BINS = int(math.ceil(round(math.log10(_HIST_HI / _HIST_LO), 9)
                        * _BINS_PER_DECADE))


class DurationStats:
    __slots__ = ("counts", "total", "sum", "max")

    def __init__(self):
        self.counts = [0] * (_N_BINS + 2)  # [under] + bins + [over]
        self.total = 0
        self.sum = 0.0
        self.max = 0.0

    def record(self, ms: float) -> None:
        v = float(ms)
        if v <= _HIST_LO:
            idx = 0
        elif v >= _HIST_HI:
            idx = _N_BINS + 1
        else:
            idx = 1 + int(math.log10(v / _HIST_LO) * _BINS_PER_DECADE)
            idx = min(max(idx, 1), _N_BINS)
        self.counts[idx] += 1
        self.total += 1
        self.sum += v
        self.max = max(self.max, v)

    def percentile(self, p: float) -> float:
        if self.total == 0:
            return 0.0
        need = math.ceil(p * self.total)
        acc = 0
        for i, c in enumerate(self.counts):
            acc += c
            if acc >= need:
                if i == 0:
                    return _HIST_LO
                if i == _N_BINS + 1:
                    return _HIST_HI
                return _HIST_LO * 10.0 ** (i / _BINS_PER_DECADE)
        return _HIST_HI

    def snapshot(self) -> dict:
        return {
            "count": self.total,
            "total_ms": round(self.sum, 6),
            "mean_ms": round(self.sum / self.total if self.total else 0.0, 6),
            "max_ms": round(self.max, 6),
            "p50_ms": round(self.percentile(0.50), 6),
            "p95_ms": round(self.percentile(0.95), 6),
            "p99_ms": round(self.percentile(0.99), 6),
        }


# --- the span record --------------------------------------------------------

@dataclasses.dataclass
class Span:
    name: str
    cat: str
    t_start: float           # clock seconds
    t_end: float | None = None
    attrs: dict = dataclasses.field(default_factory=dict)
    track: str = "host"      # exporter groups spans into one lane per track
    depth: int = 0           # nesting depth inside its track at open time
    seq: int = 0             # deterministic tie-break / event id

    @property
    def dur_ms(self) -> float:
        return ((self.t_end or self.t_start) - self.t_start) * 1e3

    def set(self, key: str, value: Any) -> None:
        self.attrs[key] = value


class _NullSpan:
    """The disarmed stand-in: accepts attribute writes, records nothing."""

    def set(self, key: str, value: Any) -> None:
        pass


NULL_SPAN = _NullSpan()

_lock = threading.Lock()
_spans: list[Span] = []           # finished spans, bounded (ring)
_stats: dict[str, DurationStats] = {}
_dropped = 0
_seq = 0
_tls = threading.local()


def _cfg():
    return _tdt_config.get_config().obs


def span_enabled() -> bool:
    cfg = _cfg()
    return cfg is not None and cfg.spans


def _clock_now() -> float:
    return _retry.get_clock().monotonic()


def _open_stack() -> list:
    st = getattr(_tls, "open_spans", None)
    if st is None:
        st = _tls.open_spans = []
    return st


def _finish(sp: Span) -> None:
    global _dropped, _seq
    cfg = _cfg()
    max_spans = cfg.max_spans if cfg is not None else 4096
    with _lock:
        sp.seq = _seq
        _seq += 1
        st = _stats.get(sp.name)
        if st is None:
            st = _stats[sp.name] = DurationStats()
        st.record(sp.dur_ms)
        _spans.append(sp)
        if len(_spans) > max_spans:
            # evict oldest; every evicted span is counted (a lowered
            # max_spans can evict many at once), and the streaming stats
            # above keep the percentiles whole (no silent caps)
            n_evict = len(_spans) - max_spans
            del _spans[:n_evict]
            _dropped += n_evict


class _OpenSpan:
    """One ``with span(...)`` block: a profiler annotation while a
    profiler session runs, a ring :class:`Span` while ``config.obs`` arms
    it, both when both listen. ``set`` reaches both."""

    __slots__ = ("name", "cat", "attrs", "_ann", "_sp")

    def __init__(self, name: str, cat: str, attrs: dict):
        self.name, self.cat, self.attrs = name, cat, attrs
        self._ann = self._sp = None

    def __enter__(self):
        if _profiling():
            self._ann = _TraceAnnotation(self.name, **self.attrs)
            self._ann.__enter__()
        if span_enabled():
            stack = _open_stack()
            self._sp = Span(name=self.name, cat=self.cat,
                            t_start=_clock_now(), attrs=self.attrs,
                            depth=len(stack))
            stack.append(self._sp)
        elif self._ann is None:
            return NULL_SPAN
        return self

    def set(self, key: str, value: Any) -> None:
        if self._ann is not None:
            self._ann.set_metadata(**{key: value})
        if self._sp is not None:
            self._sp.attrs[key] = value

    def __exit__(self, *exc) -> bool:
        sp = self._sp
        if sp is not None:
            _open_stack().pop()
            sp.t_end = _clock_now()
            _finish(sp)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


def span(name: str, cat: str = "host", **attrs: Any) -> _OpenSpan:
    """A nested span as a context manager: in the device trace whenever a
    profiler session runs, in the ring (on the resilience clock) when
    ``config.obs`` arms it. ``with`` yields a handle whose ``set(key,
    value)`` attaches an attribute known only inside the body (which
    guard-ladder rung ran, how many tokens a round made) to both;
    :data:`NULL_SPAN` when nothing listens."""
    return _OpenSpan(name, cat, attrs)


def record_span(name: str, t_start: float, t_end: float, *,
                cat: str = "host", track: str = "host",
                **attrs: Any) -> None:
    """Record an already-measured span (explicit clock timestamps — the
    serving engine's lifecycle phases arrive this way, timed on the
    engine's own injectable clock). No-op when disarmed."""
    if not span_enabled():
        return
    _finish(Span(name=name, cat=cat, t_start=float(t_start),
                 t_end=float(t_end), attrs=dict(attrs), track=track))


def instant(name: str, *, cat: str = "host", track: str = "host",
            **attrs: Any) -> None:
    """A point event (exported as a chrome instant)."""
    if not span_enabled():
        return
    now = _clock_now()
    _finish(Span(name=name, cat=cat, t_start=now, t_end=now,
                 attrs=dict(attrs), track=track))


def annotate(**attrs: Any) -> None:
    """Attach attributes to the innermost OPEN span of this thread (no-op
    when disarmed or outside any span)."""
    if not span_enabled():
        return
    stack = _open_stack()
    if stack:
        stack[-1].attrs.update(attrs)


def annotate_span(name: str, **attrs: Any) -> None:
    """Attach attributes to the innermost OPEN span NAMED ``name`` (no-op
    when disarmed or when no such span is open). The jit dispatch layer
    uses this to stamp retry evidence onto the enclosing ``op:{family}``
    guard span specifically — at that point the innermost open span is
    its own ``jit:{family}``, which is not where a ladder-rung reader
    looks."""
    if not span_enabled():
        return
    for sp in reversed(_open_stack()):
        if sp.name == name:
            sp.attrs.update(attrs)
            return


def spans() -> list[Span]:
    with _lock:
        return list(_spans)


def dropped_spans() -> int:
    with _lock:
        return _dropped


def span_stats(prefix: str = "") -> dict:
    """Per-name duration stats (count / total / mean / max / p50 / p95 /
    p99 ms), streaming — unaffected by ring evictions. ``prefix`` filters
    names (the serving engine reads its ``serving:`` phases this way)."""
    with _lock:
        return {
            name: st.snapshot()
            for name, st in sorted(_stats.items())
            if name.startswith(prefix)
        }


def reset() -> None:
    global _dropped, _seq
    with _lock:
        _spans.clear()
        _stats.clear()
        _dropped = 0
        _seq = 0
