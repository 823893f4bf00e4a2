"""Recording capture of a kernel's signal protocol (ISSUE 10 tentpole).

This is ``tests/test_overlap_structure.py::_spy_comm`` promoted into a
first-class recording mode: the ``shmem/device.py`` primitive surface is
replaced by shims that RECORD instead of issuing hardware ops, and the
kernel body runs once per rank as plain eager Python — no Pallas trace, no
interpreter, no devices.

How a capture runs (``capture_world``):

- ``config.update(timeout_iters=...)`` arms the watchdog posture for the
  duration, so the chunked put families issue their pure chunk signals and
  every wait funnels through the (shimmed) bounded-wait path, allocating
  the SAME trace-time site ordinals a real armed run would
  (``watchdog.KernelDiagScope.next_wait_site`` — the contract of
  ``resilience/sites.py``);
- ``dist_pallas_call`` is replaced per op module by a launcher that builds
  :class:`FakeRef` stand-ins for every input/output/scratch ref and calls
  the kernel body directly inside a ``watchdog.kernel_scope``;
- ``shmem.my_pe`` returns the CONCRETE rank under capture, so every SPMD
  peer expression (``jax.lax.rem(me - s + n, n)`` …) folds to a concrete
  integer — the "resolved symbolically per rank" of the issue;
- ``jax.lax.fori_loop`` / ``pl.when`` are replaced by eager Python
  equivalents (comm never lives inside them — the comm loops unroll in
  Python, the invariant the overlap-structure tests already rely on), and
  ``pltpu.make_async_copy`` / ``pltpu.emit_pipeline`` by recording fakes,
  so the whole body executes concretely;
- the semaphore slot of every put/signal/wait is identified by
  ``(ref position in the kernel signature, index tuple)`` — SPMD symmetry
  makes that key identical on every rank, which is exactly how the
  hardware's symmetric semaphore arrays work.

The result is a :class:`WorldCapture`: one deterministic event trace per
rank (two captures of the same tuple are byte-identical — pinned in
tests/test_analysis.py), the input of ``analysis/verify.py``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Any, Callable

import numpy as np

from triton_dist_tpu.resilience import sites as S

# Event kinds (the trace alphabet). Each event is one Event row below.
PUT = "put"            # one-sided put: credits recv slot at dst + send slot here
SIGNAL = "signal"      # pure semaphore increment at dst (chunk signals, ...)
WAIT = "wait"          # bounded wait (consumes `value` from a local slot)
WAIT_RECV = "wait_recv"  # DMA arrival wait on a put's recv slot (consumes 1)
WAIT_SEND = "wait_send"  # local send-completion wait (consumes 1)
DMA_START = "dma_start"  # local async copy issued (credits its sem slot)
DMA_WAIT = "dma_wait"    # local async copy waited (consumes 1)
CHUNKED = "chunked_put"  # marker: a chunked put family was emitted
# NOTE: barrier_all has no event kind of its own — the capture shim emits
# its rounds as targeted SIGNALs + one bounded WAIT per round on a
# shared "<barrier>" slot, which is faithful to the hardware (one barrier
# semaphore counter per PE, credits conserved across rounds — see the
# cross-invocation caveat on shmem.barrier_all) and lets the credit model
# reason about barrier reachability like any other slot.


@dataclasses.dataclass
class Event:
    """One protocol event in a rank's program order. ``slot`` is the
    semaphore identity ``(ref_name, index_tuple)``; ``dst`` the target
    rank of a put/signal; ``site`` the bounded-wait ordinal; ``kind`` the
    ``resilience/sites.py`` KIND_* of a wait; ``meta`` carries per-kind
    extras (chunk markers, landing-view declarations, row counts)."""

    op: str
    slot: tuple | None = None
    dst: int | None = None
    value: int = 1
    kind: int | None = None
    site: int | None = None
    meta: dict = dataclasses.field(default_factory=dict)

    def line(self) -> str:
        """Canonical one-line form (byte-identical captures compare on
        these)."""
        parts = [self.op]
        if self.slot is not None:
            parts.append(f"slot={self.slot[0]}{list(self.slot[1])}")
        if self.dst is not None:
            parts.append(f"dst={self.dst}")
        if self.value != 1:
            parts.append(f"value={self.value}")
        if self.kind is not None:
            parts.append(f"kind={S.kind_name(self.kind)}")
        if self.site is not None:
            parts.append(f"site={self.site}")
        for k in sorted(self.meta):
            parts.append(f"{k}={self.meta[k]}")
        return " ".join(parts)


@dataclasses.dataclass
class Launch:
    """One ``dist_pallas_call`` invocation on one rank."""

    family: str
    events: list[Event] = dataclasses.field(default_factory=list)
    n_wait_sites: int = 0


@dataclasses.dataclass
class RankTrace:
    rank: int
    launches: list[Launch] = dataclasses.field(default_factory=list)

    def lines(self) -> list[str]:
        out = []
        for l in self.launches:
            out.append(f"launch {l.family} sites={l.n_wait_sites}")
            out.extend("  " + e.line() for e in l.events)
        return out


@dataclasses.dataclass
class WorldCapture:
    """The verifier's input: one aligned trace per rank of one tuple."""

    family: str
    world: int
    label: str
    traces: list[RankTrace]

    def canonical(self) -> str:
        out = [f"family={self.family} world={self.world} label={self.label}"]
        for t in self.traces:
            out.append(f"rank {t.rank}")
            out.extend("  " + ln for ln in t.lines())
        return "\n".join(out) + "\n"


class CaptureError(RuntimeError):
    """The recording trace could not produce a usable protocol graph."""


# ---------------------------------------------------------------------------
# Fake refs / descriptors / handles
# ---------------------------------------------------------------------------

def _shape_dtype(spec) -> tuple[tuple, Any]:
    """Shape/dtype of an out_shape / scratch entry (ShapeDtypeStruct or
    pallas MemoryRef; semaphore dtypes fall back to int32)."""
    import jax.numpy as jnp

    shape = tuple(getattr(spec, "shape", ()))
    dtype = getattr(spec, "dtype", None)
    try:
        dtype = jnp.dtype(dtype)
    except TypeError:
        dtype = jnp.dtype(jnp.int32)  # semaphores
    return shape, dtype


def _resolve_index(i):
    """One index element → canonical key part. Concrete values fold to
    ints; pl.ds slices to ('ds', start, size); anything unresolvable
    (a traced value — only reachable inside local compute loops) to '?'."""
    if hasattr(i, "start") and hasattr(i, "size"):  # pallas Slice
        return ("ds", _resolve_index(i.start), int(i.size))
    if isinstance(i, slice):
        return ":"
    try:
        return int(i)
    except Exception:
        return "?"


class FakeRef:
    """Stand-in for a Pallas ref: knows shape/dtype/identity, serves zeros
    on read, swallows writes, and composes ``.at[...]`` views while
    recording the index path (semaphore slot identity)."""

    def __init__(self, shape, dtype, name, path=()):
        self.shape = tuple(shape)
        self.dtype = dtype
        self.name = name
        self.path = tuple(path)

    @property
    def ndim(self):
        return len(self.shape)

    def key(self) -> tuple:
        return (self.name, self.path)

    # --- view composition ---------------------------------------------
    def _view(self, idx) -> "FakeRef":
        if not isinstance(idx, tuple):
            idx = (idx,)
        shape = []
        parts = []
        dims = list(self.shape)
        for i in idx:
            if i is Ellipsis:
                # keep remaining dims (only ever used terminally here;
                # extend BEFORE recording the marker — parts indexes dims)
                shape.extend(dims[len(parts):])
                parts.append("...")
                return FakeRef(
                    shape, self.dtype, self.name, self.path + tuple(parts)
                )
            parts.append(_resolve_index(i))
            if isinstance(i, slice):
                d = dims[len(parts) - 1]
                start = 0 if i.start is None else int(i.start)
                stop = d if i.stop is None else int(i.stop)
                shape.append(stop - start)
            elif hasattr(i, "start") and hasattr(i, "size"):  # pl.ds Slice
                shape.append(int(i.size))
            else:
                pass  # integer (incl. 0-d array) index: dim dropped
        shape.extend(dims[len(parts):])
        return FakeRef(shape, self.dtype, self.name, self.path + tuple(parts))

    @property
    def at(self):
        ref = self

        class _At:
            def __getitem__(_, idx):
                return ref._view(idx)

        return _At()

    # --- data access (eager zeros; identity does not matter) -----------
    def __getitem__(self, idx):
        import jax.numpy as jnp

        view = self._view(idx)
        return jnp.zeros(view.shape, view.dtype)

    def __setitem__(self, idx, value):
        return None

    def __array__(self, dtype=None):
        return np.zeros(self.shape, dtype or self.dtype)

    def __repr__(self):
        return f"FakeRef({self.name}{list(self.path)}, {self.shape})"


class FakeDesc:
    """Recording stand-in for ``pltpu.make_async_copy``'s descriptor: a
    local DMA chain in the credit model (start credits its semaphore slot,
    wait consumes one). A ``.wait()`` with no local ``.start()`` on that
    slot consumes a REMOTE put's credit — the matching-byte-count recv
    idiom of the scatter kernels."""

    def __init__(self, state, src, dst, sem):
        self._state = state
        self._key = sem.key() if isinstance(sem, FakeRef) else ("<sem>", ())

    def start(self):
        self._state.record(Event(DMA_START, slot=self._key))

    def wait(self):
        self._state.record(Event(DMA_WAIT, slot=self._key))

    # PutHandle-compat spellings used by a few kernels
    wait_send = wait
    wait_recv = wait


# ---------------------------------------------------------------------------
# The capture state + shims
# ---------------------------------------------------------------------------

class _CaptureState:
    def __init__(self, rank: int, world: int):
        self.rank = rank
        self.world = world
        self.trace = RankTrace(rank)
        self._launch: Launch | None = None

    def record(self, ev: Event) -> Event:
        if self._launch is None:
            raise CaptureError(
                "shmem primitive recorded outside a dist_pallas_call launch"
            )
        self._launch.events.append(ev)
        return ev

    @contextlib.contextmanager
    def launch(self, family: str):
        from triton_dist_tpu.resilience import watchdog

        if self._launch is not None:
            raise CaptureError(f"nested kernel launch in capture: {family}")
        self._launch = Launch(family)
        try:
            with watchdog.kernel_scope(None, family) as scope:
                yield
            self._launch.n_wait_sites = scope._wait_sites
        finally:
            self.trace.launches.append(self._launch)
            self._launch = None


def _put_rows(dst_ref) -> int | None:
    if isinstance(dst_ref, FakeRef) and dst_ref.shape:
        return int(dst_ref.shape[0])
    return None


@contextlib.contextmanager
def capture_shims(state: _CaptureState, op_modules: list):
    """Install the recording shims around one rank's capture. Patches are
    name-based (each op module binds ``dist_pallas_call``/``_axis_size``
    at import) plus attribute-based on the ``shmem.device`` module object
    — the same two seams the spy tests use — and every patch is restored
    on exit, including the ``jax.lax.fori_loop`` / ``pl.when`` /
    ``pltpu.make_async_copy`` eager replacements."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from triton_dist_tpu import config as tdt_config
    from triton_dist_tpu import resilience
    from triton_dist_tpu.resilience import watchdog
    from triton_dist_tpu.shmem import device as shmem

    rank, world = state.rank, state.world

    # ---- shmem surface -------------------------------------------------
    def my_pe(axis):
        watchdog.register_pe(rank)
        return jnp.int32(rank)

    def n_pes(axis):
        return world

    class FakeHandle(shmem.PutHandle):
        # subclass so shmem.quiet / ChunkedPutHandle bookkeeping (which
        # isinstance-check and read .send_waited) treat it as the real thing
        def __init__(self, recv_key, send_key):
            self.desc = None
            self.send_waited = False
            self.sig_sem = None
            self._recv_key = recv_key
            self._send_key = send_key

        def wait_send(self):
            state.record(Event(WAIT_SEND, slot=self._send_key))
            self.send_waited = True

        def wait_recv(self):
            state.record(Event(WAIT_RECV, slot=self._recv_key))

        def wait(self):
            self.wait_send()
            self.wait_recv()

    def _sem_key(sem):
        if isinstance(sem, FakeRef):
            return sem.key()
        raise CaptureError(f"semaphore is not a captured ref: {sem!r}")

    def putmem_nbi_block(dst_ref, src_ref, pe, axis, send_sem, recv_sem):
        rk, sk = _sem_key(recv_sem), _sem_key(send_sem)
        state.record(Event(
            PUT, slot=rk, dst=int(pe),
            meta={"send_slot": sk, "rows": _put_rows(dst_ref)},
        ))
        return FakeHandle(rk, sk)

    def signal_op(sem, inc=1, pe=None, axis=None):
        state.record(Event(
            SIGNAL, slot=_sem_key(sem), value=int(inc),
            dst=rank if pe is None else int(pe),
        ))

    def _wait_or_watchdog(sem, value, kind):
        scope = watchdog.active()
        if scope is None:
            raise CaptureError("bounded wait outside a kernel scope")
        state.record(Event(
            WAIT, slot=_sem_key(sem), value=int(value), kind=int(kind),
            site=scope.next_wait_site(),
        ))

    def barrier_all(axis="tp"):
        n = world
        if n == 1:
            return
        scope = watchdog.active()
        # mirror the real all-pairs barrier (shmem.barrier_rounds): per
        # round, one signal to each peer in the round's offset range and
        # one bounded wait (site-numbered, KIND_BARRIER) consuming as many
        # credits, on a synthetic per-launch slot shared by all ranks
        me = rank
        slot = ("<barrier>", ())
        for lo, hi in shmem.barrier_rounds(n):
            for off in range(lo, hi):
                state.record(
                    Event(SIGNAL, slot=slot, value=1, dst=(me + off) % n)
                )
            state.record(Event(
                WAIT, slot=slot, value=hi - lo, kind=S.KIND_BARRIER,
                site=scope.next_wait_site(),
            ))

    def barrier_neighbors(axis="tp"):
        n = world
        if n == 1:
            return
        scope = watchdog.active()
        slot = ("<barrier>", ())
        state.record(Event(SIGNAL, slot=slot, value=1, dst=(rank - 1) % n))
        state.record(Event(SIGNAL, slot=slot, value=1, dst=(rank + 1) % n))
        state.record(Event(
            WAIT, slot=slot, value=2, kind=S.KIND_BARRIER,
            site=scope.next_wait_site(),
        ))

    orig_chunked = shmem.putmem_signal_chunked_nbi_block
    orig_chunked_a2a = shmem.putmem_signal_chunked_a2a_nbi_block
    orig_signal2 = shmem.putmem_signal2_nbi_block

    def putmem_signal_chunked_nbi_block(
        dst_at, src_at, pe, axis, send_at, recv_at, sig_at, spans,
        ready=None, recv_view=None,
    ):
        state.record(Event(CHUNKED, meta={
            "form": "ring", "n_chunks": len(spans),
            "landing_view": recv_view is not None,
        }))
        return orig_chunked(
            dst_at, src_at, pe, axis, send_at, recv_at, sig_at, spans,
            ready=ready, recv_view=recv_view,
        )

    def putmem_signal_chunked_a2a_nbi_block(
        dst_at, src_at, peers, axis, send_at, recv_at, sig_at, spans,
        recv_view=None,
    ):
        state.record(Event(CHUNKED, meta={
            "form": "a2a", "n_peers": len(peers), "n_chunks": len(spans),
            "landing_view": recv_view is not None,
        }))
        return orig_chunked_a2a(
            dst_at, src_at, peers, axis, send_at, recv_at, sig_at, spans,
            recv_view=recv_view,
        )

    def putmem_signal2_nbi_block(
        dst_ref, src_ref, pe, axis, send_sem, recv_sem, sig_sem=None,
        canary=False,
    ):
        # delegate to the REAL protocol (which calls the patched
        # putmem/signal primitives), then annotate the put event with its
        # chunk-signal/landing-view declaration for the coverage check
        n_before = len(state._launch.events)
        h = orig_signal2(
            dst_ref, src_ref, pe, axis, send_sem, recv_sem, sig_sem, canary
        )
        for ev in state._launch.events[n_before:]:
            if ev.op == PUT:
                ev.meta["chunk_signal"] = sig_sem is not None
                ev.meta["landing_view"] = bool(canary)
        return h

    # ---- dist_pallas_call: invoke the kernel body on fake refs ---------
    def dist_pallas_call(
        kernel, *, name, out_shape, in_specs=None, out_specs=None,
        grid=None, grid_spec=None, scratch_shapes=(), **_kw,
    ):
        if grid is not None or grid_spec is not None:
            raise CaptureError(
                f"capture supports only grid-free comm kernels; "
                f"{name!r} uses a grid (grid kernels carry no signal "
                f"protocol — verify their host composition instead)"
            )

        def invoke(*args):
            single = not isinstance(out_shape, (tuple, list))
            outs = [out_shape] if single else list(out_shape)
            refs = []
            for i, a in enumerate(args):
                refs.append(FakeRef(a.shape, a.dtype, f"a{i}"))
            base = len(refs)
            for i, o in enumerate(outs):
                sh, dt = _shape_dtype(o)
                refs.append(FakeRef(sh, dt, f"a{base + i}"))
            base = len(refs)
            for i, s in enumerate(scratch_shapes):
                sh, dt = _shape_dtype(s)
                refs.append(FakeRef(sh, dt, f"a{base + i}"))
            with state.launch(name):
                kernel(*refs)
            res = tuple(jnp.zeros(*_shape_dtype(o)) for o in outs)
            return res[0] if single else res

        return invoke

    # ---- eager control flow / local DMA ---------------------------------
    def fori_loop(lower, upper, body, init, **_kw):
        val = init
        for i in range(int(lower), int(upper)):
            val = body(jnp.int32(i), val)
        return val

    def when(condition):
        def _wrapped(f):
            if bool(condition):
                f()

        return _wrapped

    def make_async_copy(src_ref, dst_ref, sem):
        return FakeDesc(state, src_ref, dst_ref, sem)

    def emit_pipeline(body, *, grid=None, in_specs=None, out_specs=None, **_kw):
        def run(*refs, **__kw):
            return None

        return run

    def guarded_call(family, primary, fallback, *args, **kwargs):
        # capture must see the FUSED protocol and fail loudly — a silent
        # golden fallback would verify an empty graph
        return primary(*args, **kwargs)

    def axis_index(axis):
        return jnp.int32(rank)

    # ---- install everything, restore on exit ---------------------------
    _MISSING = object()
    patches: list[tuple[Any, str, Any]] = []

    def patch(obj, attr, val):
        patches.append((obj, attr, getattr(obj, attr, _MISSING)))
        setattr(obj, attr, val)

    old_cfg = {
        "timeout_iters": tdt_config.get_config().timeout_iters,
        "fault_plan": tdt_config.get_config().fault_plan,
        "integrity": tdt_config.get_config().integrity,
        "debug_comm_delay": tdt_config.get_config().debug_comm_delay,
    }
    try:
        # armed-watchdog posture: chunk signals issued, waits bounded
        tdt_config.update(
            timeout_iters=1024, fault_plan=None, integrity=None,
            debug_comm_delay=0,
        )
        patch(shmem, "my_pe", my_pe)
        patch(shmem, "n_pes", n_pes)
        patch(shmem, "putmem_nbi_block", putmem_nbi_block)
        patch(shmem, "signal_op", signal_op)
        patch(shmem, "_wait_or_watchdog", _wait_or_watchdog)
        patch(shmem, "barrier_all", barrier_all)
        patch(shmem, "sync_all", barrier_all)  # module-load alias
        patch(shmem, "barrier_neighbors", barrier_neighbors)
        patch(shmem, "putmem_signal_chunked_nbi_block",
              putmem_signal_chunked_nbi_block)
        patch(shmem, "putmem_signal_chunked_a2a_nbi_block",
              putmem_signal_chunked_a2a_nbi_block)
        patch(shmem, "putmem_signal2_nbi_block", putmem_signal2_nbi_block)
        patch(resilience, "guarded_call", guarded_call)
        patch(jax.lax, "fori_loop", fori_loop)
        patch(jax.lax, "axis_index", axis_index)
        patch(pl, "when", when)
        patch(pltpu, "make_async_copy", make_async_copy)
        patch(pltpu, "emit_pipeline", emit_pipeline)
        for mod in op_modules:
            if hasattr(mod, "dist_pallas_call"):
                patch(mod, "dist_pallas_call", dist_pallas_call)
            if hasattr(mod, "_axis_size"):
                patch(mod, "_axis_size", lambda axis, world=world: world)
        yield
    finally:
        for obj, attr, val in reversed(patches):
            if val is _MISSING:
                delattr(obj, attr)
            else:
                setattr(obj, attr, val)
        tdt_config.update(**old_cfg)


def capture_rank(
    fn: Callable, rank: int, world: int, op_modules: list
) -> RankTrace:
    """Run ``fn()`` (a shard-level kernel invocation closed over its
    inputs) under the recording shims as ``rank`` of ``world``."""
    state = _CaptureState(rank, world)
    with capture_shims(state, op_modules):
        fn()
    if not state.trace.launches:
        raise CaptureError(
            "capture recorded no kernel launch — the op served a "
            "non-fused path (check the config/world routing)"
        )
    return state.trace


def capture_world(
    make_fn: Callable[[int], Callable],
    world: int,
    op_modules: list,
    *,
    family: str,
    label: str = "",
) -> WorldCapture:
    """Capture all ``world`` ranks of one kernel tuple. ``make_fn(rank)``
    returns the zero-argument shard-level invocation for that rank (the
    same inputs on every rank — SPMD)."""
    traces = [
        capture_rank(make_fn(r), r, world, op_modules) for r in range(world)
    ]
    names = [tuple(l.family for l in t.launches) for t in traces]
    if len(set(names)) != 1:
        raise CaptureError(
            f"ranks traced different launch sequences (not SPMD?): {names}"
        )
    return WorldCapture(family=family, world=world, label=label, traces=traces)
