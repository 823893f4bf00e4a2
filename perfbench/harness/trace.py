"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read. The trace is first brought into a small plain
form, so that the arithmetic can be checked on a recorded trace kept as
JSON beside the tests:

    {plane: {line: {"names": [...], "idx": [...], "start": [...], "dur": [...]}}}

``idx`` points into ``names``; ``start`` and ``dur`` are nanoseconds on
the trace's own clock, which device and host planes share. Device planes
are ``/device:TPU:<id>``; their line ``XLA Modules`` has one event per
execution of a compiled program (named ``jit_<fn>(<fingerprint>)``) and
``XLA Ops`` one per device operation inside it. Host planes keep only the
spans this benchmark wrote (names starting with ``perfbench.``).
"""

from __future__ import annotations

import glob
import json
import os
import re

import numpy as np

HOST_PREFIX = "perfbench."
MODULES, OPS = "XLA Modules", "XLA Ops"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


_HLO = re.compile(r"^%?(\S+) = (.*?)\b([a-z][a-z0-9\-]*)\((.*)$", re.S)


def short_name(text: str) -> str:
    """A device op's event name is its whole HLO instruction; keep the
    instruction's name, its result shape, its opcode and its first
    operands: ``copy.1902 bf16[16,128,8,128,128] copy(fusion.3)``. Other
    names (programs, host spans) pass unchanged."""
    m = _HLO.match(text)
    if not m:
        return text
    name, shape, opcode, rest = m.groups()
    shape = re.sub(r"\{[^}]*\}", "", shape).replace(" ", "")
    operands = re.findall(r"%([\w.\-]+)", rest.split("), ")[0])[:2]
    return f"{name} {shape} {opcode}({', '.join(operands)})"[:160]


def kind_of(short: str) -> str:
    """``<opcode> <result shape>`` of a shortened op name: what a
    breakdown groups by, since one program holds thousands of ops. A
    fusion or a custom call (a kernel) goes by its own name instead."""
    parts = short.split(" ", 2)
    if len(parts) < 3 or "(" not in parts[2]:
        return short
    opcode = parts[2].split("(")[0]
    if opcode in ("custom-call", "fusion"):
        opcode = re.sub(r"[.\d]+$", "", parts[0])
    return f"{opcode} {parts[1]}"


class Events:
    """The events of one line: parallel arrays, sorted by start."""

    def __init__(self, names, idx, start, dur):
        order = np.argsort(np.asarray(start, np.int64), kind="stable")
        self.names = list(names)
        self.idx = np.asarray(idx, np.int64)[order]
        self.start = np.asarray(start, np.int64)[order]
        self.dur = np.asarray(dur, np.int64)[order]

    def __len__(self) -> int:
        return len(self.idx)

    @property
    def end(self):
        return self.start + self.dur

    def select(self, mask) -> "Events":
        return Events(self.names, self.idx[mask], self.start[mask], self.dur[mask])

    def matching(self, pattern: str) -> "Events":
        rx = re.compile(pattern)
        hit = np.array([bool(rx.search(n)) for n in self.names], bool)
        return self.select(hit[self.idx]) if len(self) else self

    def within(self, t0: int, t1: int) -> "Events":
        """Events that start inside ``[t0, t1)``."""
        return self.select((self.start >= t0) & (self.start < t1))

    def inside(self, outer: "Events") -> "Events":
        """Events that start inside one of ``outer``'s intervals (which do
        not overlap each other: executions of programs on one device)."""
        if not len(self) or not len(outer):
            return self.select(np.zeros(len(self), bool))
        k = np.searchsorted(outer.start, self.start, side="right") - 1
        ok = (k >= 0) & (self.start < outer.end[np.maximum(k, 0)])
        return self.select(ok)

    def total_s(self) -> float:
        return float(self.dur.sum()) / 1e9

    def by_name(self, key=None) -> list[tuple[str, int, float]]:
        """``(name, count, seconds)``, longest first; with ``key`` the
        names are grouped by ``key(name)`` first."""
        if not len(self):
            return []
        secs = np.bincount(self.idx, self.dur, len(self.names)) / 1e9
        count = np.bincount(self.idx, minlength=len(self.names))
        groups: dict[str, list] = {}
        for i, name in enumerate(self.names):
            if count[i]:
                g = groups.setdefault(key(name) if key else name, [0, 0.0])
                g[0] += int(count[i])
                g[1] += float(secs[i])
        return sorted(((k, c, t) for k, (c, t) in groups.items()),
                      key=lambda row: -row[2])


EMPTY = Events([], [], [], [])


def busy_intervals(ev: Events) -> np.ndarray:
    """Union of the events' intervals as ``[[start, end], ...]`` (ns):
    nested and overlapping events count once."""
    if not len(ev):
        return np.zeros((0, 2), np.int64)
    end = np.maximum.accumulate(ev.end)
    new = np.concatenate([[True], ev.start[1:] > end[:-1]])
    starts = ev.start[new]
    last = np.concatenate([np.flatnonzero(new)[1:] - 1, [len(ev) - 1]])
    return np.stack([starts, end[last]], 1)


def busy_s(ev: Events) -> float:
    iv = busy_intervals(ev)
    return float((iv[:, 1] - iv[:, 0]).sum()) / 1e9


class Trace:
    def __init__(self, planes: dict):
        self.planes = planes

    def device_planes(self) -> list[str]:
        found = [(int(m.group(1)), p) for p in self.planes
                 if (m := _DEVICE.match(p))]
        return [p for _, p in sorted(found)]

    def line(self, plane: str, line: str) -> Events:
        return self.planes.get(plane, {}).get(line, EMPTY)

    def host_spans(self) -> Events:
        """Every span this benchmark wrote, over all host threads."""
        names, idx, start, dur = [], [], [], []
        for plane, lines in self.planes.items():
            if _DEVICE.match(plane):
                continue
            for ev in lines.values():
                base = len(names)
                names += ev.names
                idx += [base + int(i) for i in ev.idx]
                start += [int(t) for t in ev.start]
                dur += [int(t) for t in ev.dur]
        return Events(names, idx, start, dur)

    def window(self, span: str = HOST_PREFIX + "window") -> tuple[int, int]:
        """The traced window: the benchmark's own span around the serve
        call; without it, the extent of the device events."""
        ev = self.host_spans().matching("^" + re.escape(span) + "$")
        if len(ev):
            return int(ev.start[0]), int(ev.end[0])
        ext = [self.line(p, OPS) for p in self.device_planes()]
        ext = [e for e in ext if len(e)]
        if not ext:
            raise ValueError("the trace holds no device operation")
        return (min(int(e.start[0]) for e in ext),
                max(int(e.end.max()) for e in ext))

    @classmethod
    def from_json(cls, obj: dict) -> "Trace":
        return cls({
            plane: {line: Events(**ev) for line, ev in lines.items()}
            for plane, lines in obj.items()
        })


def load_json(path: str) -> Trace:
    with open(path) as f:
        return Trace.from_json(json.load(f))


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str) -> Trace:
    """Read a ``.xplane.pb`` with JAX's own reader. Device planes are kept
    whole; of host planes only the benchmark's own spans."""
    from jax.profiler import ProfileData

    planes: dict = {}
    for plane in ProfileData.from_file(path).planes:
        device = bool(_DEVICE.match(plane.name))
        lines = {}
        for line in plane.lines:
            if device and line.name not in (MODULES, OPS):
                continue
            names, where, idx, start, dur = [], {}, [], [], []
            for e in line.events:
                name = e.name
                if not device and not name.startswith(HOST_PREFIX):
                    continue
                k = where.get(name)
                if k is None:
                    k = where[name] = len(names)
                    names.append(short_name(name) if device else name)
                idx.append(k)
                start.append(e.start_ns)
                dur.append(e.duration_ns)
            if idx:
                lines[line.name] = Events(names, idx, start, dur)
        if lines:
            planes[plane.name] = lines
    return Trace(planes)


def idle_gaps(busy: np.ndarray, window: tuple[int, int], spans: Events,
              top: int = 10) -> list[list]:
    """The idle time of one device inside ``window``, by what the host was
    doing: every gap between busy intervals is given to the SHORTEST of
    the benchmark's host spans that covers its middle (the innermost
    one), and the seconds are summed by that span's name."""
    t0, t1 = window
    edges = [t0]
    for s, e in busy:
        if e <= t0 or s >= t1:
            continue
        edges += [max(s, t0), min(e, t1)]
    edges.append(t1)
    total: dict[str, float] = {}
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        mid = (g0 + g1) // 2
        cover = (spans.start <= mid) & (spans.end > mid)
        if cover.any():
            k = np.flatnonzero(cover)
            name = spans.names[spans.idx[k[np.argmin(spans.dur[k])]]]
        else:
            name = "outside the benchmark's spans"
        total[name] = total.get(name, 0.0) + (g1 - g0) / 1e9
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    return [[name, float(secs)] for name, secs in ranked]
