"""The program's OWN scopes, read from the device planes of a traced run.

The model's traced passes open ``jax.named_scope``s from one table
(``triton_dist_tpu/obs/scopes.py``: ``tdt.attn`` / ``tdt.ffn`` /
``tdt.ssm`` / ``tdt.head`` and the sub-parts under them), so the HLO
``op_name`` of every instruction says which part of the layer it is:
``jit(decode_step)/.../tdt.attn/qkv/dot_general``. On a TPU the profiler
keeps that name as the stat ``tf_op`` of the op's EVENT METADATA (one
record per distinct instruction, not per execution), which
``jax.profiler.ProfileData`` does not show: it gives an event's own stats
only. So this module reads the ``.xplane.pb`` as what it is, a protobuf,
with a decoder of its own for the few fields it needs (``XSpace.planes``,
``XPlane.name / lines / event_metadata / stat_metadata``,
``XEventMetadata.id / name / stats``, ``XStat``, and the ``metadata_id``
every ``XEvent`` starts with): the metadata of the fullest device's plane
once, and the events of its ``XLA Ops`` line only as far as their ids, to
put the distinct names into the order ``harness/trace.py`` folds them in.
A scope is then ``(part, sub-part)``: the first ``/``-segment that starts
with ``tdt.``, and the next segment if the table knows it. Plain form, for
the recorded fixture, one scope per distinct name of the ops line:

    [["attn", "qkv"], ["ffn", null], null, ...]

A program without scopes (a parent commit) gives ``None``, and every
reader built on this returns ``None``.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

from harness import cells, trace as tr

try:        # the program's one table of names; a parent commit has none
    from triton_dist_tpu.obs import scopes as table
except ImportError:
    table = None

STAT = "tf_op"


# -- the protobuf, as far as it is read ---------------------------------------

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        if byte < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint, a
    view of the bytes for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an xplane")
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _first(buf, field: int):
    """The first value of one field of a message, ``None`` without it."""
    return next((v for f, v in _fields(buf) if f == field), None)


def _entry(view) -> tuple[int, memoryview]:
    """A map entry ``{1: key, 2: value}``."""
    got = dict(_fields(view))
    return got.get(1, 0), got.get(2, memoryview(b""))


def _event_ids(line) -> list[int]:
    """The distinct ``metadata_id``s of a line's events, in the order of
    their first event. A million events a trace: nothing else of an event
    is decoded."""
    seen: dict[int, None] = {}
    for field, event in _fields(line):
        if field != 4:
            continue
        if len(event) and event[0] == 0x08:       # metadata_id comes first
            ident, _ = _varint(event, 1)
        else:
            ident = dict(_fields(event)).get(1, 0)
        if ident not in seen:
            seen[ident] = None
    return list(seen)


def op_names(path: str, plane: str, line: str = tr.OPS) -> list[tuple[str, str]]:
    """``(name, tf_op)`` of every distinct event name of one line of one
    plane, in the order of first appearance (how ``trace.load_xplane``
    folds them). ``tf_op`` is "" where the metadata has no such stat."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for field, body in _fields(space):
        if field != 1 or _text(_first(body, 2) or b"") != plane:
            continue
        parts: dict[int, list] = {3: [], 4: [], 5: []}
        for f2, value in _fields(body):
            if f2 in parts:
                parts[f2].append(value)
        stat_ids = set()
        for entry in parts[5]:
            ident, meta = _entry(entry)
            if _text(_first(meta, 2) or b"") == STAT:
                stat_ids.add(ident)
        meta_of: dict[int, tuple[str, str]] = {}
        for entry in parts[4]:
            ident, meta = _entry(entry)
            name, op = "", ""
            for f3, value in _fields(meta):
                if f3 == 2:
                    name = _text(value)
                elif f3 == 5:
                    stat = dict(_fields(value))
                    if stat.get(1) in stat_ids and 5 in stat:
                        op = _text(stat[5])
            meta_of[ident] = (name, op)
        for body_line in parts[3]:
            if _text(_first(body_line, 2) or b"") != line:
                continue
            out: dict[str, str] = {}
            for ident in _event_ids(body_line):
                name, op = meta_of.get(ident, ("", ""))
                out.setdefault(name, op)
            return list(out.items())
    return []


# -- scopes -----------------------------------------------------------------------

def part_of(op_name: str) -> tuple[str, str | None] | None:
    """``"jit(f)/tdt.attn/qkv/dot_general:"`` -> ``("attn", "qkv")``; a
    name with no part of the table -> ``None``."""
    if table is None:
        return None
    segs = op_name.split("/")
    for i, seg in enumerate(segs):
        if seg.startswith(table.PREFIX):
            part = seg[len(table.PREFIX):]
            if part not in table.PARTS:
                return None
            nxt = segs[i + 1] if i + 1 < len(segs) else None
            return part, nxt if nxt in table.PARTS[part] else None
    return None


class Scopes:
    """The scope of each distinct name of the fullest device's ops line:
    ``scopes[k]`` belongs to ``Events.names[k]`` of ``trace.line(plane,
    OPS)`` and of whatever is selected from it."""

    def __init__(self, scopes: list):
        self.scopes = [tuple(s) if s else None for s in scopes]

    def select(self, ev: tr.Events, want) -> tr.Events:
        """The events whose scope ``want`` accepts."""
        hit = np.array([bool(want(s)) for s in self.scopes], bool)
        return ev.select(hit[ev.idx]) if len(ev) else ev

    def under(self, ev: tr.Events, part: str, sub: str | None = None) -> tr.Events:
        """The events under ``tdt.<part>`` (under ``<part>/<sub>``)."""
        return self.select(ev, lambda s: s is not None and s[0] == part
                           and (sub is None or s[1] == sub))

    def unscoped(self, ev: tr.Events) -> tr.Events:
        return self.select(ev, lambda s: s is None)

    @classmethod
    def from_json(cls, rows: list, names: list) -> "Scopes | None":
        if len(rows) != len(names):
            raise ValueError(f"{len(rows)} scopes for {len(names)} op names")
        return cls(rows) if any(rows) else None


def load_xplane(path: str, plane: str, names: list) -> "Scopes | None":
    """The scopes of ``names`` (the ops line's, as ``trace.load_xplane``
    keeps them) from the file they came from."""
    found = op_names(path, plane)
    if [tr.short_name(n) for n, _ in found] != list(names):
        raise ValueError(
            f"{path}: the ops line of {plane} holds {len(found)} distinct "
            f"names that are not the trace's {len(names)}")
    return Scopes.from_json([part_of(op) for _, op in found], names)


def of(run) -> "Scopes | None":
    """The scopes of a traced run, parsed once for all readers; ``None``
    where the run has no trace on disk, the file is another run's, or no
    op carries a ``tdt.`` scope."""
    if "tdt_scopes" not in run.__dict__:
        run.tdt_scopes = None
        trace_dir = os.path.join(cells.ROOT, ".perfbench", "trace", run.cell.name)
        try:
            path = tr.find_xplane(trace_dir)
        except FileNotFoundError:
            return None
        t0 = time.monotonic()
        names = run.trace.line(run.plane, tr.OPS).names
        try:
            run.tdt_scopes = load_xplane(path, run.plane, names)
        except (ValueError, IndexError) as e:      # not this trace's, or cut short
            print(f"[perfbench scopes] not read: {e!r}", file=sys.stderr, flush=True)
            return None
        n = sum(s is not None for s in run.tdt_scopes.scopes) if run.tdt_scopes else 0
        print(f"[perfbench scopes] {n} of {len(names)} distinct device ops carry "
              f"a tdt.* scope; read from the device plane in "
              f"{time.monotonic() - t0:.2f} s", file=sys.stderr, flush=True)
        if run.tdt_scopes:
            print(table_text(run), file=sys.stderr, flush=True)
    return run.tdt_scopes


# -- the arithmetic the readers share --------------------------------------

def inside(run, kind: str):
    """``(scopes, executions, ops)``: the ops that start inside an
    execution of the program ``kind`` (``run.programs``) inside the
    window, selected once a run and kind; ``None`` where there is nothing
    to read."""
    scopes = of(run)
    if not scopes:
        return None
    kept = run.__dict__.setdefault("tdt_inside", {})
    if kind not in kept:
        progs = run.modules(kind)
        ops = run.ops().inside(progs)
        kept[kind] = (scopes, progs, ops) if len(progs) and len(ops) else None
    return kept[kind]


def part_ms(run, kind: str, part: str, sub: str | None = None) -> float | None:
    """Device time under a part per execution of ``kind``, in ms; nothing
    where no op of the program is under it."""
    got = inside(run, kind)
    if got is None:
        return None
    scopes, progs, ops = got
    under = scopes.under(ops, part, sub)
    return under.total_s() / len(progs) * 1e3 if len(under) else None


def table_text(run, top: int = 5) -> str:
    """One table a traced run: device seconds and ms an execution by part
    and sub-part inside the decode step, by part inside an admission, the
    largest unscoped ops of each by name, and the summed op time beside
    the programs' own time."""
    out = []
    for kind, subs in (("decode_step", True), ("prefill", False)):
        got = inside(run, kind)
        if got is None:
            continue
        scopes, progs, ops = got
        n, total = len(progs), ops.total_s()
        ms = lambda ev: ev.total_s() / n * 1e3
        out.append(f"[perfbench scopes] {kind}: {n} executions, ops inside "
                   f"{total:.3f} s = {ms(ops):.4f} ms each; the program's own "
                   f"time {progs.total_s():.3f} s = {ms(progs):.4f} ms each")
        for part, names in table.PARTS.items():
            ev = scopes.under(ops, part)
            if not len(ev):
                continue
            out.append(f"[perfbench scopes]   tdt.{part:<14}{ev.total_s():9.3f} s "
                       f"{ms(ev):9.4f} ms {100 * ev.total_s() / total:6.2f}%")
            for sub in names if subs else ():
                sev = scopes.under(ops, part, sub)
                if len(sev):
                    out.append(f"[perfbench scopes]     {part + '/' + sub:<16}"
                               f"{sev.total_s():9.3f} s {ms(sev):9.4f} ms")
        rest = scopes.unscoped(ops)
        out.append(f"[perfbench scopes]   {'unscoped':<18}{rest.total_s():9.3f} s "
                   f"{ms(rest):9.4f} ms {100 * rest.total_s() / total:6.2f}%")
        for name, count, secs in rest.by_name(tr.kind_of)[:top]:
            out.append(f"[perfbench scopes]     {name} x{count}: {secs:.3f} s "
                       f"{secs / n * 1e3:.4f} ms")
    return "\n".join(out)
