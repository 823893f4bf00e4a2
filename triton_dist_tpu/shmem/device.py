"""Device-side SHMEM library: one-sided remote ops inside Pallas kernels.

This is the TPU-native re-design of the reference's device-side OpenSHMEM
surface — ``patches/triton/python/triton/language/extra/libshmem_device.py``
(337 LoC portable stub) and the ``dl.*`` dialect ops
(``python/triton_dist/language.py:57-112``). The full mapping tables live
in ``docs/primitives.md`` (anchors ``#one-sided-puts`` through
``#barriers`` — section anchors, not line numbers, so they cannot rot as
that file grows).

Mapping (see SURVEY.md §7 design table):

====================================  =======================================
reference (NVSHMEM / dialect)          here (Pallas TPU)
====================================  =======================================
``my_pe()`` / ``n_pes()``              ``my_pe(axis)`` / ``n_pes(axis)``
                                       (mesh-axis scoped, like teams)
``putmem_nbi_block(dst,src,sz,pe)``    ``putmem_nbi_block(...)`` →
                                       ``pltpu.make_async_remote_copy``
``putmem_signal_nbi_block(...)``       same op: the *receive semaphore* IS
                                       the data-coupled signal — signal
                                       arrival implies data arrival, which
                                       NVSHMEM needs fence()+signal for
``signal_op(sig, SET/ADD, pe)``        ``signal_op(sem, inc, pe, axis)`` —
                                       TPU semaphores are ADD-native; SET is
                                       replaced by monotonic versioned
                                       counters (the reference itself does
                                       this: ``call_count`` in
                                       ``low_latency_all_to_all.py:163``)
``signal_wait_until(sig, EQ, v)``      ``signal_wait_until(sem, v)`` —
                                       consuming wait (sem -= v)
``dl.wait(ptr, n, scope, sem)``        ``wait(sem, v)`` (same consuming wait)
``dl.consume_token``                   intentionally dropped: Pallas ref
                                       semantics already order loads after
                                       semaphore waits (no compiler fence op
                                       needed — SURVEY.md §7)
``barrier_all[_block/_warp]``          ``barrier_all(*axes)`` all-pairs
                                       barrier on the hardware barrier
                                       semaphore
``fence()`` / ``quiet()``              ``quiet(*handles)`` waits local send
                                       semaphores. There is no fence: TPU
                                       remote-DMA ordering is expressed only
                                       through data-coupled recv semaphores
``getmem*`` / ``symm_at`` loads        **no remote loads on TPU** — pull
                                       algorithms are restructured as push
                                       (``getmem*`` raise, with guidance)
``int_p / remote_ptr``                 not needed: symmetric buffers are
                                       SPMD refs; addressing is (ref, pe)
====================================  =======================================

All functions must be called inside a ``pl.pallas_call`` kernel that is
itself traced under ``jax.shard_map`` over a ``jax.sharding.Mesh`` (that is
what makes every buffer symmetric across PEs by construction).
"""

from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl  # noqa: F401  (re-exported idiom)
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# PE queries (≙ nvshmem_my_pe / n_pes / team_my_pe; mesh axes play the role
# of SHMEM teams)
# ---------------------------------------------------------------------------

def my_pe(axis: str | Sequence[str]):
    """This device's index along `axis` (flattened if several axes)."""
    if isinstance(axis, str):
        idx = jax.lax.axis_index(axis)
    else:
        idx = jnp.int32(0)
        for name in axis:
            idx = idx * n_pes(name) + jax.lax.axis_index(name)
    # PE hint for the watchdog's diagnostic records (trace-time side
    # channel; no-op outside a dist_pallas_call diag scope)
    from triton_dist_tpu.resilience import watchdog as _watchdog

    _watchdog.register_pe(idx)
    return idx


def n_pes(axis: str | Sequence[str]) -> int:
    """Static size of `axis` (product if several axes)."""
    if isinstance(axis, str):
        return int(jax.lax.axis_size(axis))
    return int(math.prod(int(jax.lax.axis_size(a)) for a in axis))


def pe_dev_id(axis: str | Sequence[str], pe):
    """MESH device_id selecting index `pe` along `axis` (other axes stay at
    this device's own coordinates). A composite axis (tuple — ``my_pe``'s
    flattened row-major numbering) is decomposed into per-axis
    coordinates, the form Mosaic's device_id lowering is specified for."""
    if isinstance(axis, str):
        return {axis: pe}
    out = {}
    rem = pe
    for a in reversed(list(axis)):
        s = n_pes(a)
        out[a] = jax.lax.rem(rem, s)
        rem = jax.lax.div(rem, s)
    return out


# ---------------------------------------------------------------------------
# Hardware race shaking (≙ reference allgather.py:72-76 — random sleeps
# injected into the comm streams to stress producer/consumer sync)
# ---------------------------------------------------------------------------

def comm_jitter(axis: str | Sequence[str], salt: int = 0):
    """Per-PE pseudo-random busy delay at a comm point inside a kernel
    body. No-op (traces nothing) unless ``config.debug_comm_delay > 0``.

    The reference shakes races by sleeping its producer streams random
    multi-second amounts (``allgather.py:72-76``) so consumer-side sync
    bugs surface as wrong answers instead of lucky timing. The TPU
    analogue: a VPU busy loop whose iteration count varies per (PE,
    salt), run at the top of each fused comm kernel — PEs then issue
    their DMAs at visibly different times, exercising arrival-order
    assumptions, barrier aliasing across launches, and semaphore
    versioning under timing variance the interpreter's happens-before
    detector structurally cannot create (its schedule follows data
    dependencies, not wall time).

    The loop result is consumed as a data-dependent ZERO increment on
    the kernel's barrier semaphore: side-effecting, so neither XLA nor
    Mosaic can dead-code the delay; legal in every memory space (no ref
    access at all); and invisible to the barrier protocol regardless of
    concurrency (+0 is the identity whatever the peers are doing).
    Callable only from kernels that own a collective_id — i.e. exactly
    the barrier-bearing fused comm kernels this knob exists to shake."""
    from triton_dist_tpu import config as _tdt_config

    base = int(_tdt_config.get_config().debug_comm_delay)
    if base <= 0:
        return
    if n_pes(axis) == 1:
        # match barrier_all's world-1 early-out: a world-1 kernel carries
        # no collective_id, so touching the barrier semaphore would be a
        # Mosaic error — and there is nothing to shake anyway
        return
    me = my_pe(axis)
    # deterministic 1×–8× spread per (PE, salt); primes decorrelate PEs
    iters = base * (1 + jax.lax.rem(me * 7919 + jnp.int32(salt) * 104729, 8))

    def body(_, acc):
        return acc + jnp.sin(acc)  # non-foldable transcendental chain

    # the seed keeps acc finite by construction (|sin| <= 1, bounded
    # growth), so acc * 0.0 is exactly 0 — never NaN
    acc = jax.lax.fori_loop(0, iters, body, me.astype(jnp.float32) * 1e-3)
    pltpu.semaphore_signal(
        pltpu.get_barrier_semaphore(), (acc * 0.0).astype(jnp.int32)
    )


# ---------------------------------------------------------------------------
# One-sided puts (≙ putmem_* family)
# ---------------------------------------------------------------------------

class PutHandle:
    """Handle for an in-flight one-sided put.

    Wraps Pallas's ``AsyncCopyDescriptor`` and records — at trace time, which
    is exact because distributed kernels unroll their comm loops in Python —
    whether ``wait_send`` has already consumed the send semaphore. Semaphore
    waits are *consuming* (sem -= value), so waiting the same put's send side
    twice deadlocks on real hardware exactly as in the interpreter; the
    record lets :func:`quiet` be safely called on every handle at kernel end
    without double-waiting ones that were recycled mid-loop.

    ``sig_sem``, set by the chunked put family, names the pure signal
    semaphore that rode along with the data (armed diag scopes only) —
    :func:`wait_chunk` consumes it through the watchdogged/injectable wait
    path before the data-coupled recv wait.
    """

    __slots__ = ("desc", "send_waited", "sig_sem")

    def __init__(self, desc, sig_sem=None):
        self.desc = desc
        self.send_waited = False
        self.sig_sem = sig_sem

    def wait_send(self):
        """Wait local completion: the source buffer is reusable after this."""
        self.desc.wait_send()
        self.send_waited = True

    def wait_recv(self):
        """Wait one incoming symmetric transfer on this put's recv semaphore
        (SPMD symmetry: peers use the same semaphore slot, so this observes
        the arrival *into* this PE, not our outbound put's remote delivery)."""
        self.desc.wait_recv()

    def wait(self):
        self.wait_send()
        self.wait_recv()


def putmem_nbi_block(dst_ref, src_ref, pe, axis: str, send_sem, recv_sem):
    """Non-blocking one-sided put: write local `src_ref` into PE `pe`'s
    `dst_ref` (≙ ``libshmem_device.putmem_nbi_block``; mapping row in
    ``docs/primitives.md#one-sided-puts``).

    Returns the started ``AsyncCopyDescriptor``. The *remote* device's
    `recv_sem` is incremented when the data has fully landed — this is the
    data-coupled signal that replaces NVSHMEM's separate
    ``putmem_signal``/``fence`` pair. Call ``.wait_send()`` (or
    :func:`quiet`) before reusing `src_ref`.
    """
    copy = pltpu.make_async_remote_copy(
        src_ref=src_ref,
        dst_ref=dst_ref,
        send_sem=send_sem,
        recv_sem=recv_sem,
        device_id=pe_dev_id(axis, pe) if not isinstance(pe, dict) else pe,
        device_id_type=pltpu.DeviceIdType.MESH,
    )
    copy.start()
    return PutHandle(copy)


def putmem_block(dst_ref, src_ref, pe, axis: str, send_sem, recv_sem):
    """Blocking put: returns after the local source is safe to reuse
    (≙ ``putmem_block``; NVSHMEM's blocking puts likewise only guarantee
    local completion)."""
    copy = putmem_nbi_block(dst_ref, src_ref, pe, axis, send_sem, recv_sem)
    copy.wait_send()
    return copy


def putmem_signal_nbi_block(dst_ref, src_ref, sig_sem, pe, axis: str, send_sem):
    """Put + signal in one op (≙ ``putmem_signal_nbi_block``; mapping row
    in ``docs/primitives.md#one-sided-puts``): on TPU the signal is simply the remote receive
    semaphore of the same DMA, so arrival of the signal *implies* arrival of
    the data (stronger than NVSHMEM, which needs NVSHMEM_SIGNAL_ADD +
    ordering)."""
    return putmem_nbi_block(dst_ref, src_ref, pe, axis, send_sem, recv_sem=sig_sem)


class ChunkedPutHandle:
    """Handle for a shard transfer split into per-chunk puts
    (:func:`putmem_signal_chunked_nbi_block`).

    Each chunk is its own DMA with its own send/recv semaphore slot, so the
    consumer can wait — and compute on — chunk ``j`` while chunks ``j+1..``
    are still in flight. This is the TPU form of the reference's
    tile-granular progress (``dl.wait`` per M-tile, allgather_gemm.py:226):
    the readiness flag granularity becomes the DMA granularity.
    """

    __slots__ = ("chunks", "recv_at", "spans")

    def __init__(self, chunks: "list[PutHandle]", recv_at=None, spans=None):
        self.chunks = list(chunks)
        # canary wiring (ISSUE 8): ``recv_at(off, rows)`` maps a span to
        # the LOCAL view where the mirror peer's chunk lands — only the
        # kernel knows it (the outbound dst slice is a different shard in
        # ring protocols), so kernels that opt into payload integrity
        # declare it via putmem_signal_chunked_nbi_block(recv_view=...)
        self.recv_at = recv_at
        self.spans = spans

    def __len__(self):
        return len(self.chunks)

    def _recv_view(self, j: int):
        if self.recv_at is None or self.spans is None:
            return None
        off, rows = self.spans[j]
        return self.recv_at(off, rows)

    def wait_recv_chunk(self, j: int):
        """Chunk-aware arrival wait for chunk `j` (see :func:`wait_chunk`)."""
        wait_chunk(self.chunks[j], recv_ref=self._recv_view(j))

    def wait_send_chunk(self, j: int):
        """Local completion of chunk `j`'s put: its source rows are
        reusable. Idempotent at trace time (consuming-wait safety, as
        :func:`quiet`)."""
        h = self.chunks[j]
        if not h.send_waited:
            h.wait_send()

    def wait_recv(self):
        """Arrival of the WHOLE shard: chunk waits in order."""
        for j in range(len(self.chunks)):
            self.wait_recv_chunk(j)

    def wait_send(self):
        """Local completion of every chunk's put (skips chunks already
        waited mid-loop — :func:`quiet` calls this blindly)."""
        for j in range(len(self.chunks)):
            self.wait_send_chunk(j)

    def wait(self):
        self.wait_send()
        self.wait_recv()


def putmem_signal_chunked_nbi_block(
    dst_at, src_at, pe, axis: str, send_at, recv_at, sig_at, spans,
    ready=None, recv_view=None,
):
    """Chunked put + per-chunk signal (≙ one ``putmem_signal_nbi_block``
    per sub-shard chunk — the producer side of tile-granular progress;
    mapping row in ``docs/primitives.md#one-sided-puts``): split one shard
    transfer into the static
    ``spans`` from :func:`ops.common.chunk_schedule`, each chunk pushed as
    its own DMA whose data-coupled recv semaphore slot signals that chunk's
    arrival alone.

    ``dst_at(off, rows)`` / ``src_at(off, rows)`` map a span to the ref
    views to transfer (callers fold their traced shard base offset into the
    slice — Pallas refs are sliced once, not nested). ``send_at(j)`` /
    ``recv_at(j)`` / ``sig_at(j)`` map a chunk index to its semaphore slot;
    slot agreement across PEs is SPMD symmetry, exactly as for the unchunked
    puts. ``ready(j)``, if given, runs before chunk ``j``'s put starts —
    ring kernels pass the previous step's ``wait_recv_chunk(j)`` so each
    chunk is forwarded the moment it lands (wormhole pipelining across
    hops).

    Inside an armed WATCHDOG scope (``config.timeout_iters > 0`` and a
    diag scope open — trace-time, so producer and consumer agree) each
    chunk additionally carries a pure ``signal_op`` on its ``sig_at(j)``
    slot: that op is the chaos-injection site (drop/dup/delay per
    FaultPlan) and the bounded-wait site of :func:`wait_chunk`, giving
    chunk-granular watchdog diagnostics. Without the watchdog no extra
    signals are issued — the data-coupled recv semaphore is the only (and
    sufficient) signal, as everywhere else on TPU; a fault plan armed
    WITHOUT the watchdog must not add a droppable edge whose wait would
    then be unbounded (chunk-signal chaos requires ``timeout_iters > 0``,
    like every drop-fault scenario in tests/test_chaos.py).

    ``recv_view(off, rows)``, if given, is the LOCAL view where the mirror
    peer's chunk lands (ring kernels receive a *different* shard than they
    send, so only the kernel can name it). Declaring it opts this put
    family into payload integrity (ISSUE 8): with the canary armed
    (``config.integrity.canary`` + the watchdog) each chunk's signal
    increment becomes ``1 + payload_checksum(chunk)`` — the SAME signal
    edge with a bigger increment, no new droppable edges, the chaos-pinned
    discipline of the w8 scale DMAs — and ``wait_recv_chunk`` recomputes
    the checksum over the landed view, recording a ``KIND_INTEGRITY``
    diagnostic on mismatch; the landing view is also where the payload
    fault kinds (bitflip / torn_chunk / stale_read / nan_inject) mutate
    interpret-mode landings (resilience/faults.py).
    """
    # the canary kwarg rides only when a landing view opted in (also
    # keeps the kwarg invisible to callers/monkeypatches of the plain
    # chunked protocol)
    kw = {"canary": True} if recv_view is not None else {}
    handles = []
    for j, (off, rows) in enumerate(spans):
        if ready is not None:
            ready(j)
        handles.append(
            putmem_signal2_nbi_block(
                dst_at(off, rows), src_at(off, rows), pe, axis,
                send_at(j), recv_at(j),
                sig_at(j) if sig_at is not None else None, **kw,
            )
        )
    return ChunkedPutHandle(handles, recv_at=recv_view, spans=spans)


def putmem_signal_chunked_a2a_nbi_block(
    dst_at, src_at, peers, axis: str, send_at, recv_at, sig_at, spans,
    recv_view=None,
):
    """Peer-direct chunked all-to-all put (≙ the per-peer
    ``putmem_signal_nbi_block`` loop of the reference's LL dispatch,
    low_latency_all_to_all.py:94-118, at tile granularity): push a distinct
    per-peer payload to EVERY peer, each split into the static ``spans``
    from :func:`ops.common.chunk_schedule`, on per-(peer, chunk) semaphore
    slots.

    Issue order is CHUNK-MAJOR — every peer's chunk ``j`` is started before
    any peer's chunk ``j+1`` — so the earliest chunks ride the distinct
    hardware routes to all peers concurrently and each receiver's FIRST
    chunk lands as soon as the wire allows; a chunk-granular consumer
    (:class:`ChunkedPutHandle.wait_recv_chunk`) starts computing on it
    while the later rounds are still in flight. This is the a2a form of
    the ring families' wormhole pipelining: there are no multi-hop
    forwards to pipeline (puts are hardware-routed in one hop), the win is
    first-chunk latency and per-round route concurrency.

    ``dst_at(i, off, rows)`` / ``src_at(i, off, rows)`` map (peer index
    into `peers`, span) to the ref views; ``send_at(i, j)`` /
    ``recv_at(i, j)`` / ``sig_at(i, j)`` map (peer index, chunk) to
    semaphore slots — slot agreement across PEs is SPMD symmetry, exactly
    as for the unchunked puts. Chunk signals follow the
    :func:`putmem_signal2_nbi_block` contract (armed watchdog scopes only;
    drop/dup/delay injectable; bounded waits record ``chunk_wait``).

    Returns one :class:`ChunkedPutHandle` per peer, in `peers` order; by
    SPMD symmetry handle ``i``'s recv side observes the equal-shaped
    incoming chunks from the mirror peer, so receivers consume per-peer
    payloads chunk by chunk through ``wait_recv_chunk``.

    ``recv_view(i, off, rows)``, if given, names the LOCAL view where the
    chunk incoming from peer ``i`` lands — the payload-integrity opt-in of
    :func:`putmem_signal_chunked_nbi_block`, per peer.
    """
    kw = {"canary": True} if recv_view is not None else {}
    handles: list[list[PutHandle]] = [[] for _ in peers]
    for j, (off, rows) in enumerate(spans):
        for i, pe in enumerate(peers):
            handles[i].append(
                putmem_signal2_nbi_block(
                    dst_at(i, off, rows), src_at(i, off, rows), pe, axis,
                    send_at(i, j), recv_at(i, j),
                    sig_at(i, j) if sig_at is not None else None, **kw,
                )
            )
    return [
        ChunkedPutHandle(
            hs,
            recv_at=(
                None if recv_view is None
                else (lambda off, rows, i=i: recv_view(i, off, rows))
            ),
            spans=spans,
        )
        for i, hs in enumerate(handles)
    ]


def putmem_signal2_nbi_block(
    dst_ref, src_ref, pe, axis: str, send_sem, recv_sem, sig_sem=None,
    canary: bool = False,
):
    """Single-chunk building block of the chunked put family: a
    ``putmem_nbi_block`` that, inside an armed WATCHDOG scope, also issues
    the pure per-chunk signal on ``sig_sem`` (the injectable, bounded edge
    :func:`wait_chunk` consumes; never issued without the watchdog — see
    :func:`putmem_signal_chunked_nbi_block`). Fused kernels that interleave
    compute between chunk puts call this directly and aggregate the
    handles in a :class:`ChunkedPutHandle`.

    ``canary=True`` (set by the chunked put families when the kernel
    declared a ``recv_view``) folds the payload checksum into the chunk
    signal when the integrity canary is armed: the increment becomes
    ``1 + payload_checksum(src)`` on the SAME signal edge —
    :func:`wait_chunk` consumes the arrival unit, reads the residual
    checksum, and drains it after comparing against the landed data.
    Producer and consumer agreement is trace-time (both gate on
    :func:`chunk_canary_armed`), so no credit can leak across launches."""
    h = putmem_nbi_block(dst_ref, src_ref, pe, axis, send_sem, recv_sem)
    if sig_sem is not None and chunk_signals_armed():
        h.sig_sem = sig_sem
        inc = 1
        if canary and chunk_canary_armed():
            from triton_dist_tpu.resilience import integrity as _integrity

            # checksum over the SOURCE payload (clean by construction:
            # payload faults model landing-site corruption, faults.py), so
            # a corrupted landing disagrees with this increment
            inc = 1 + _integrity.payload_checksum(src_ref[...])
        signal_op(sig_sem, inc, pe, axis)
    return h


def chunk_signals_armed() -> bool:
    """Whether per-chunk pure signals are issued/waited in this trace
    (an armed watchdog scope — trace-time, so producers and consumers of a
    chunk slot agree by construction; see
    :func:`putmem_signal_chunked_nbi_block`)."""
    from triton_dist_tpu.resilience import watchdog as _watchdog

    return _watchdog.active() is not None and _watchdog.enabled()


def chunk_canary_armed() -> bool:
    """Whether chunk signals carry payload checksums in this trace: the
    integrity canary (``config.integrity.canary``) on top of an armed
    watchdog scope (the canary rides the watchdog's signal slots and diag
    buffer — without the watchdog it is silently inert, exactly like the
    chunk signals themselves). Trace-time, so the producer's increment and
    the consumer's drain agree by construction."""
    from triton_dist_tpu.resilience import integrity as _integrity

    return chunk_signals_armed() and _integrity.canary_enabled()


def wait_chunk(handle: "PutHandle", recv_ref=None):
    """Chunk-aware arrival wait (≙ the reference's per-tile ``dl.wait`` +
    ``dl.consume_token``, allgather_gemm.py:226-227): block until this
    chunk's data has landed on this PE.

    Two layers, both consuming: when the chunk carried a pure signal (armed
    diag scope) the signal is waited first through the watchdogged path —
    bounded by ``config.timeout_iters``, chaos-injectable, recorded as
    ``KIND_CHUNK`` ("chunk_wait") in the diagnostic buffer on expiry — and
    then the data-coupled recv semaphore is waited, which is authoritative:
    data puts cannot be dropped (faults.py), so a lost/duped chunk *signal*
    either trips the watchdog with a chunk-site record or leaves the result
    untouched, never corrupts it.

    ``recv_ref`` (the LOCAL landed-chunk view, from the kernel's
    ``recv_view`` declaration) adds the payload tier (ISSUE 8), in order:

    1. an armed PAYLOAD fault plan mutates the landing here — after the
       data wait, modeling a PE whose memory corrupts what lands in it
       (``faults.apply_payload_fault``; interpret-mode only, like all
       injection);
    2. with the canary armed, the signal's residual credits are the
       producer's payload checksum: recompute over the landed view,
       record a ``KIND_INTEGRITY`` diagnostic on mismatch (first record
       wins, named PE = this PE = the corrupt one), and DRAIN the
       residual either way so the slot carries no credit into the next
       launch.

    Composition limit (by design of "no new signal edges"): the canary
    RIDES the chunk signal, so a MISCOUNTED chunk signal (``dup_signal``
    chaos, a real protocol bug) under an armed canary reads as a
    checksum mismatch on the receiving PE even when the landed bytes are
    perfect — signal-layer anomalies alias into the payload tier on the
    shared edge, and the in-kernel observer cannot tell them apart (the
    residual IS its only reference). The signal-kind chaos cells
    therefore pin the canary-off posture; treat an integrity record
    under signal chaos as "the chunk protocol was violated", not as
    proof of data rot."""
    from triton_dist_tpu.resilience import faults as _faults
    from triton_dist_tpu.resilience import records as _records
    from triton_dist_tpu.resilience import watchdog as _watchdog

    if handle.sig_sem is not None:
        _wait_or_watchdog(handle.sig_sem, 1, _records.KIND_CHUNK)
    handle.wait_recv()
    if recv_ref is None:
        return
    scope = _watchdog.active()
    if scope is None:
        return
    # ONE payload-site ordinal per consumed chunk, shared by the fault
    # injector and the canary record — FaultPlan.site targets exactly the
    # ordinal the diagnostic will name, and arming the canary never
    # shifts the wait-site numbering of the timeout records
    site = scope.next_payload_site()
    _faults.apply_payload_fault(recv_ref, scope.pe, site=site)
    if handle.sig_sem is not None and chunk_canary_armed():
        from triton_dist_tpu.resilience import integrity as _integrity

        sent = signal_read(handle.sig_sem)          # producer's checksum
        local = _integrity.payload_checksum(recv_ref[...])
        _watchdog.record_integrity_mismatch(
            sent, local, jnp.not_equal(sent, local), site
        )

        @pl.when(sent > 0)
        def _drain():
            # consume the residual credits whatever the verdict — a
            # mismatch must not leave the slot pre-satisfied for the next
            # launch (the bounded-wait drain discipline)
            pltpu.semaphore_wait(handle.sig_sem, sent)


def getmem_nbi_block(*_args, **_kwargs):
    raise NotImplementedError(
        "TPU has no one-sided remote *loads* (no nvshmem_ptr/symm_at "
        "dereference). Restructure the algorithm as a push from the data "
        "owner — see SURVEY.md §7 'Hard parts' and e.g. the push-based "
        "EP combine: triton_dist_tpu/ops/all_to_all.py (the slab "
        "transport) and triton_dist_tpu/layers/ep_a2a_layer.py (the "
        "push-based combine)."
    )


getmem_block = getmem_nbi_block
remote_ptr = getmem_nbi_block  # ≙ symm_at / nvshmem_ptr: intentionally absent


# ---------------------------------------------------------------------------
# Signals (≙ signal_op / signal_wait_until / dl.wait / dl.notify)
# ---------------------------------------------------------------------------

def _maybe_inject(inc):
    """Route a signal increment through the chaos injector (identity unless
    a ``config.fault_plan`` is armed and this trace is in a diag scope)."""
    from triton_dist_tpu.resilience import faults as _faults
    from triton_dist_tpu.resilience import watchdog as _watchdog

    scope = _watchdog.active()
    if scope is None:
        return inc
    return _faults.apply_signal_fault(inc, scope.pe)


def signal_op(sem, inc=1, pe=None, axis: str | None = None):
    """Increment a (possibly remote) semaphore (≙ ``signal_op`` with
    NVSHMEM_SIGNAL_ADD, and ≙ ``dl.notify(sig="add")``,
    language.py:98-112). SET semantics do not exist on TPU semaphores —
    use monotonically increasing expected values instead.

    This is a chaos injection site: an armed ``config.fault_plan`` may
    drop, duplicate, or delay the increment on its target PE (see
    resilience/faults.py; interpret-mode only)."""
    inc = _maybe_inject(inc)
    if pe is None:
        pltpu.semaphore_signal(sem, inc)
    else:
        pltpu.semaphore_signal(
            sem,
            inc,
            device_id=pe_dev_id(axis, pe) if not isinstance(pe, dict) else pe,
            device_id_type=pltpu.DeviceIdType.MESH,
        )


def _wait_or_watchdog(sem, value, kind):
    """Blocking wait, or the bounded watchdogged variant when armed
    (``config.timeout_iters > 0`` inside a dist_pallas_call diag scope):
    poll up to the budget, consume on success, or write the diagnostic
    record and RETURN — the kernel keeps issuing its later signals/puts so
    a timed-out PE can never deadlock its peers (its own later waits
    fast-fail on a zero budget; the host raises DistTimeoutError).

    Every bounded wait is also the obs layer's telemetry site (ISSUE 9):
    with ``config.obs.wait_stats`` armed on top of the watchdog, the
    observed spin count lands in the kernel's telemetry buffer — success
    path included — keyed by the same trace-time site ordinal the
    timeout diagnostics use (docs/observability.md)."""
    from triton_dist_tpu.resilience import watchdog as _watchdog

    if _watchdog.enabled() and _watchdog.active() is not None:
        _watchdog.bounded_wait(sem, value, kind=kind)
    else:
        pltpu.semaphore_wait(sem, value)


def signal_wait_until(sem, value):
    """Block until `sem` >= value, then consume (sem -= value)
    (≙ ``signal_wait_until(CMP_EQ)`` given monotonic counters). Bounded by
    the watchdog when ``config.timeout_iters > 0`` (docs/resilience.md)."""
    from triton_dist_tpu.resilience import records as _records

    _wait_or_watchdog(sem, value, _records.KIND_SIGNAL)


def wait(sem, value=1):
    """≙ ``dl.wait(barrier_ptr, n, scope, semantic)`` (language.py:57-70):
    spin until the flag semaphore reaches `value`. The acquire semantics and
    the follow-up ``dl.consume_token`` are implicit — Pallas orders ref
    reads after the wait. Bounded by the watchdog when armed."""
    from triton_dist_tpu.resilience import records as _records

    _wait_or_watchdog(sem, value, _records.KIND_WAIT)


def consume_token(token=None):  # noqa: ARG001
    """No-op, kept for API parity with ``dl.consume_token``
    (language.py:72-80). On TPU the dependency is structural."""
    return None


def signal_read(sem):
    """Non-destructive read of a semaphore's current value."""
    return pltpu.semaphore_read(sem)


def quiet(*copies):
    """Wait local (send) completion of the given nbi puts
    (≙ ``libshmem_device.quiet``): after return, source buffers are
    reusable. Does NOT imply remote delivery — remote delivery is observed
    through the receiver's semaphore, as in NVSHMEM. Handles whose send was
    already waited mid-kernel are skipped (consuming semantics — a second
    wait would deadlock)."""
    for c in copies:
        if isinstance(c, PutHandle) and c.send_waited:
            continue
        c.wait_send()


def fence():
    """≙ ``libshmem_device.fence``. Intentionally a no-op with a warning in
    the docstring rather than a runtime op: TPU remote DMAs carry their own
    completion semaphores and there is no inter-DMA ordering primitive.
    Order-sensitive protocols must chain on semaphores."""
    return None


# ---------------------------------------------------------------------------
# Barriers (≙ barrier_all / barrier_all_block / sync_all)
# ---------------------------------------------------------------------------

def barrier_rounds(n: int) -> list[tuple[int, int]]:
    """``barrier_all``'s schedule over ``n`` PEs: per round the half-open
    range of peer offsets ``[lo, hi)`` this PE signals (and the number of
    credits, ``hi - lo``, it then consumes). ceil(log2 n) rounds of
    doubling width cover every offset 1..n-1 exactly once."""
    out, lo = [], 1
    while lo < n:
        hi = min(2 * lo, n)
        out.append((lo, hi))
        lo = hi
    return out


def barrier_all(axis: str | Sequence[str] = "tp"):
    """Barrier over all PEs of `axis` on the hardware barrier semaphore
    (≙ ``libshmem_device.barrier_all`` and the device barrier kernels in
    reference ``common_ops.py:45-160``). Requires ``collective_id`` to be
    set in the kernel's ``pltpu.CompilerParams``.

    Every PE signals EVERY other PE once and consumes ``n - 1`` credits,
    in ceil(log2(n)) rounds of doubling width (:func:`barrier_rounds`:
    round r signals the peers at offsets ``[2^r, 2^(r+1))`` and consumes
    as many credits). The barrier semaphore is ONE counting semaphore, so
    credits are fungible across rounds and across launches that share the
    collective_id; the all-pairs count is what makes that sound. The
    first PE to leave launch k has consumed k(n-1) credits while no peer
    can have entered launch k+1 (it would have had to leave k first), and
    each peer sends one credit per launch it has entered — so all n-1
    peers have entered launch k. A classic dissemination barrier (one
    signal to ``me + 2^r`` per round) does NOT have this property on a
    counting semaphore: at n=4 a PE can collect its two credits from
    ``me-1`` and ``me-2`` while ``me+1`` has not entered the kernel. That
    is an argument on paper: NO failure was ever observed from the old
    barrier (PR 23's four-chip halt was ``all_gather``'s untiled slots and
    outlived this change), and the cost of ``n - 1`` remote signals per PE
    instead of ``ceil(log2 n)`` (7 against 3 at n=8) is not measured.

    No data READ is ordered on the barrier (data rides recv semaphores).
    Do not give two kernels that may run concurrently the same
    ``dist_pallas_call(name=...)``.
    """
    from triton_dist_tpu.resilience import faults as _faults
    from triton_dist_tpu.resilience import records as _records

    axes = [axis] if isinstance(axis, str) else list(axis)
    sizes = [n_pes(a) for a in axes]
    n = int(math.prod(sizes))
    if n == 1:
        return
    sem = pltpu.get_barrier_semaphore()
    me = my_pe(axes if len(axes) > 1 else axes[0])
    # chaos: a straggler fault_plan skews this PE's entry into the barrier
    # (and hence its whole downstream issue schedule). The busy loop's
    # data-dependent zero rides the first round's signal increment so
    # neither XLA nor Mosaic can dead-code the delay (comm_jitter's trick).
    straggle_zero = _faults.straggler_entry_delay(me)
    for r, (lo, hi) in enumerate(barrier_rounds(n)):
        inc = 1 if (r > 0 or straggle_zero is None) else 1 + straggle_zero
        # each round's signals are ONE chaos injection site (drop/dup/delay)
        inc = _maybe_inject(inc)
        for off in range(lo, hi):
            partner = jax.lax.rem(me + off, n)
            # unflatten partner into per-axis coordinates (row-major)
            dev_id = {}
            rem_idx = partner
            for a, s in zip(reversed(axes), reversed(sizes)):
                dev_id[a] = jax.lax.rem(rem_idx, s)
                rem_idx = jax.lax.div(rem_idx, s)
            pltpu.semaphore_signal(
                sem, inc, device_id=dev_id,
                device_id_type=pltpu.DeviceIdType.MESH,
            )
        _wait_or_watchdog(sem, hi - lo, _records.KIND_BARRIER)


sync_all = barrier_all  # ≙ sync_all (no quiet needed: see quiet() contract)


def barrier_neighbors(axis: str = "tp"):
    """Cheap ring-neighbor barrier: sync only with left/right neighbors
    (sufficient before ring sends; ≙ the reference's intra-node
    two-phase barrier on PCIe, common_ops.py:104-160)."""
    n = n_pes(axis)
    if n == 1:
        return
    from triton_dist_tpu.resilience import records as _records

    sem = pltpu.get_barrier_semaphore()
    me = my_pe(axis)
    left = jax.lax.rem(me - 1 + n, n)
    right = jax.lax.rem(me + 1, n)
    pltpu.semaphore_signal(
        sem, _maybe_inject(1), device_id={axis: left},
        device_id_type=pltpu.DeviceIdType.MESH,
    )
    pltpu.semaphore_signal(
        sem, _maybe_inject(1), device_id={axis: right},
        device_id_type=pltpu.DeviceIdType.MESH,
    )
    _wait_or_watchdog(sem, 2, _records.KIND_BARRIER)
