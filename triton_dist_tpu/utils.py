"""Host-side utilities: timing, allclose, rank-aware printing, seeding.

TPU-native re-design of the reference's ``python/triton_dist/utils.py``
(dist_print :201, assert_allclose :789-818, perf_func :186-198,
init_seed :75-88). CUDA-event timing becomes ``block_until_ready`` walltime;
per-rank seeding becomes ``jax.random`` key folding.
"""

from __future__ import annotations

import contextlib
import functools
import math
import os
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def axis_size(axis: str) -> int:
    """Static mesh-axis size inside ``shard_map`` as a Python int."""
    return int(jax.lax.axis_size(axis))


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


def next_power_of_2(x: int) -> int:
    return 1 if x <= 1 else 2 ** math.ceil(math.log2(x))


def pick_block(dim: int, block: int) -> int:
    """Largest divisor of `dim` that is <= `block` and power-of-2-shrinkable
    from it (block-shape picker shared by the fused kernels)."""
    block = min(block, dim)
    while dim % block != 0:
        block //= 2
    return max(block, 1)


def dist_print(*args: Any, rank: int | None = None, prefix: bool = True, allowed_ranks: Sequence[int] | str = (0,), **kwargs: Any) -> None:
    """Rank-filtered printing (≙ reference utils.py:201-230).

    In JAX the host process is usually singular even for many devices, so
    ranks here are process indices (multi-host) rather than device ranks.
    `rank` is shorthand for ``allowed_ranks=(rank,)``.
    """
    pid = jax.process_index()
    if rank is not None:
        allowed = (rank,)
    elif allowed_ranks == "all":
        allowed = range(jax.process_count())
    else:
        allowed = allowed_ranks
    if pid in allowed:
        if prefix:
            print(f"[rank {pid}]", *args, **kwargs)
        else:
            print(*args, **kwargs)


def init_seed(seed: int = 0, rank: int | None = None) -> jax.Array:
    """Deterministic per-rank seeding (≙ reference utils.py:75-88)."""
    rank = jax.process_index() if rank is None else rank
    np.random.seed(seed + rank)
    return jax.random.fold_in(jax.random.PRNGKey(seed), rank)


def assert_allclose(x: jax.Array, y: jax.Array, atol: float = 1e-3, rtol: float = 1e-3, verbose: bool = True) -> None:
    """Verbose allclose (≙ reference utils.py:789-818): reports worst
    mismatch location/magnitude instead of a bare boolean."""
    x = np.asarray(x, dtype=np.float64)
    y = np.asarray(y, dtype=np.float64)
    if x.shape != y.shape:
        raise AssertionError(f"shape mismatch: {x.shape} vs {y.shape}")
    err = np.abs(x - y) - (atol + rtol * np.abs(y))
    bad = err > 0
    if bad.any():
        n_bad = int(bad.sum())
        idx = np.unravel_index(np.argmax(err), err.shape)
        msg = (
            f"allclose failed: {n_bad}/{x.size} elements "
            f"({100.0 * n_bad / x.size:.3f}%) exceed atol={atol} rtol={rtol}; "
            f"worst at {idx}: {x[idx]} vs {y[idx]} (abs err {abs(x[idx]-y[idx]):.6g})"
        )
        if verbose:
            print(msg)
        raise AssertionError(msg)


def _sync(out: Any) -> None:
    """Force device completion of everything enqueued so far.

    Besides ``jax.block_until_ready``, fetch one scalar per shard to host
    — each device queue is in-order, so the readback implies all prior
    programs on it completed."""
    jax.block_until_ready(out)
    for leaf in jax.tree.leaves(out):
        if not hasattr(leaf, "addressable_shards"):
            continue
        for shard in leaf.addressable_shards:
            data = shard.data
            if data.size:
                jax.device_get(data.ravel()[0])


def perf_func(fn: Callable[[], Any], iters: int = 10, warmup_iters: int = 3) -> tuple[Any, float]:
    """Time a jitted thunk, returning (last_output, mean_ms)
    (≙ reference utils.py:186-198, CUDA events → walltime).

    Uses delta timing — two loop sizes, subtracting — so the constant
    sync/readback overhead cancels out.
    """
    out = None
    for _ in range(max(warmup_iters, 1)):
        out = fn()
    _sync(out)

    def timed(k: int) -> float:
        t0 = time.perf_counter()
        o = None
        for _ in range(k):
            o = fn()
        _sync(o)
        return time.perf_counter() - t0

    n1 = max(1, iters // 4)
    n2 = n1 + iters
    t1 = timed(n1)
    t2 = timed(n2)
    return out, max(t2 - t1, 1e-9) * 1e3 / (n2 - n1)


def _loop_runner(op, args, perturb_idx, consume):
    """Build the jitted chained-iteration while_loop for `op` (see
    :func:`perf_func_loop`): returns ``(run, arr_args)`` where
    ``run(n, arr_args)`` executes n chained iterations on device."""
    args = tuple(args)
    is_arr = [hasattr(a, "shape") and hasattr(a, "dtype") for a in args]
    arr_args = tuple(a for a, f in zip(args, is_arr) if f)

    def rebuild(arrs: tuple) -> tuple:
        it = iter(arrs)
        return tuple(next(it) if f else a for a, f in zip(args, is_arr))

    def body(state):
        i, carry = state
        out = op(*rebuild(carry))
        leaves = jax.tree.leaves(out)
        if consume == "all":
            scalar = sum(jnp.sum(l, dtype=jnp.float32) for l in leaves) * 1e-30
        else:
            scalar = leaves[0].ravel()[0].astype(jnp.float32) * 1e-30
        x = carry[perturb_idx]
        x = x.at[(0,) * x.ndim].add(scalar.astype(x.dtype))
        return i + 1, carry[:perturb_idx] + (x,) + carry[perturb_idx + 1 :]

    @jax.jit
    def run(n, arrs):
        return jax.lax.while_loop(
            lambda s: s[0] < n, body, (jnp.int32(0), arrs)
        )[1]

    return run, arr_args


def perf_func_loop(
    op: Callable[..., Any],
    args: Sequence[Any],
    iters: int = 100,
    trials: int = 3,
    perturb_idx: int = 0,
    consume: str = "first",
) -> float:
    """On-device loop timing: run `op(*args)` `iters` times inside one jitted
    ``lax.while_loop`` and return the median per-iteration ms.

    Per-call timing is dominated by per-dispatch host cost, which buries
    µs-scale kernels; a device-side loop
    measures only device time. Each iteration scatter-adds a vanishing
    multiple of the output into one element of array arg ``perturb_idx`` —
    a 1-element dynamic-update-slice that aliases the loop carry, chaining
    iterations so neither XLA nor the scheduler can hoist, CSE, or overlap
    them.

    `consume` picks how much of the output feeds that chain:

    - ``"first"`` (default) — one element. Correct for SIDE-EFFECTFUL ops
      (our Pallas kernels): they execute in full regardless, and a bigger
      dependency would bill them an extra HBM read pass that a pure op
      gets fused away.
    - ``"all"`` — a full ``sum`` over every output leaf. REQUIRED for pure
      XLA ops: anything partial lets dead-code elimination shrink the op to
      the observed slice (a matmul collapses to one dot-product row). The
      sum itself is ~free for XLA — it fuses into the producer's epilogue.

    The trip count is a runtime argument (one compile); the loop is timed
    at two different counts and scored on the delta, so the single launch's
    constant dispatch/readback cost cancels as well. Non-array args (Mesh,
    axis names) are closed over; only arrays ride the carry, and
    `perturb_idx` indexes the *array* args.
    """
    run, arr_args = _loop_runner(op, args, perturb_idx, consume)
    n1 = max(1, iters // 4)
    n2 = n1 + iters
    _sync(run(jnp.int32(n1), arr_args))  # compile + warm
    ts = []
    last_t2 = 1e-9
    for _ in range(2 * trials):  # re-measure on jitter, up to 2x attempts
        t0 = time.perf_counter()
        _sync(run(jnp.int32(n1), arr_args))
        t1 = time.perf_counter()
        _sync(run(jnp.int32(n2), arr_args))
        t2 = time.perf_counter()
        last_t2 = t2 - t1
        delta = ((t2 - t1) - (t1 - t0)) * 1e3 / iters
        # a negative delta is jitter in the constant part exceeding the
        # measured work — a FAILED sample, never "infinitely fast"
        if delta > 0:
            ts.append(delta)
        if len(ts) == trials:
            break
    if not ts:
        # every delta drowned in jitter: conservative absolute upper bound
        # (includes the constant launch cost) instead of a nonsense floor
        return last_t2 * 1e3 / n2
    ts.sort()
    return ts[len(ts) // 2]


def perf_pair_loop(
    op_a: Callable[..., Any],
    op_b: Callable[..., Any],
    args: Sequence[Any],
    iters: int = 100,
    rounds: int = 3,
    perturb_idx: int = 0,
) -> tuple[float, float, float]:
    """A/B timing of two ops over the same args with INTERLEAVED sampling:
    returns ``(t_a_ms, t_b_ms, ratio)`` where ``ratio = median of
    per-round t_b/t_a``.

    Two separately-measured :func:`perf_func_loop` calls put minutes of
    wall clock between the A and B measurements, so slow drift (host load,
    chip clocking) lands squarely in the ratio. Here both loops are
    compiled once, then rounds alternate A,B,A,B… and each round's ratio
    is taken from ADJACENT samples, cancelling any drift slower than one
    round. Both sides consume their full output (the A side can resolve to
    a pure XLA program — see the bench's world-1 sentinels — and partial
    consumption would let DCE shrink it)."""
    run_a, arrs_a = _loop_runner(op_a, args, perturb_idx, "all")
    run_b, arrs_b = _loop_runner(op_b, args, perturb_idx, "all")
    n1 = max(1, iters // 4)
    n2 = n1 + iters
    # If both sides lower to IDENTICAL HLO (e.g. a world-1 XLA-native
    # sentinel vs the XLA baseline), they are the same program by
    # definition — run ONE executable for both. Timing two separate
    # compilations of identical HLO measures buffer-placement luck
    # (observed: a consistent ~1% "loss" between literally equal dots),
    # not any property of the op.
    try:
        same = (
            run_a.lower(jnp.int32(n1), arrs_a).as_text()
            == run_b.lower(jnp.int32(n1), arrs_b).as_text()
        )
    except Exception:
        same = False
    if same:
        # same program ⇒ same speed, ratio ≡ 1 — measure once for the
        # time and report the identity instead of inter-run jitter
        t = perf_func_loop(
            op_a, args, iters=iters, trials=rounds, perturb_idx=perturb_idx,
            consume="all",
        )
        return t, t, 1.0

    def sample(run, arrs):
        t0 = time.perf_counter()
        _sync(run(jnp.int32(n1), arrs))
        t1 = time.perf_counter()
        _sync(run(jnp.int32(n2), arrs))
        t2 = time.perf_counter()
        return ((t2 - t1) - (t1 - t0)) * 1e3 / iters, (t2 - t1) * 1e3 / n2

    _sync(run_a(jnp.int32(n1), arrs_a))  # compile + warm
    _sync(run_b(jnp.int32(n1), arrs_b))
    ta, tb, ratios = [], [], []
    bound_a = bound_b = float("inf")
    for r in range(2 * rounds):  # extra attempts when jitter eats a sample
        # alternate the within-round order (A,B / B,A): any drift linear
        # over a round biases the two orders oppositely, so it cancels in
        # the median instead of pushing every ratio the same way
        if r % 2 == 0:
            da, ba = sample(run_a, arrs_a)
            db, bb = sample(run_b, arrs_b)
        else:
            db, bb = sample(run_b, arrs_b)
            da, ba = sample(run_a, arrs_a)
        bound_a, bound_b = min(bound_a, ba), min(bound_b, bb)
        if da > 0 and db > 0:
            ta.append(da)
            tb.append(db)
            ratios.append(db / da)
        if len(ratios) == rounds:
            break
    if not ratios:
        # every delta drowned in jitter: conservative absolute upper bounds
        return bound_a, bound_b, bound_b / bound_a
    for xs in (ta, tb, ratios):
        xs.sort()
    return ta[len(ta) // 2], tb[len(tb) // 2], ratios[len(ratios) // 2]


@contextlib.contextmanager
def group_profile(
    name: str | None = None,
    do_prof: bool = True,
    log_dir: str = "prof",
    merge_hosts: bool = True,
):
    """Profiling context (≙ reference utils.py:417-501 `group_profile`).

    The reference collects per-rank torch chrome traces to rank 0 and
    merges them into one JSON. The XLA profiler already records every
    LOCAL device in one trace; the cross-host half is done the XProf way:
    with ``merge_hosts=True`` on a multi-process program, every host's
    XPlane files are gathered to process 0 (bytes over the
    jax.distributed client) and written into ONE profile run directory —
    the viewer renders a run dir holding all hosts' planes as a single
    merged timeline. Single-process: a plain ``jax.profiler`` trace.

    YIELDS the trace/run directory path (``None`` with ``do_prof=False``)
    so callers — bench, chip-session scripts — can attach artifacts to
    the run::

        with group_profile("decode") as run_dir: ...

    The session this opens is the one switch for the serving loop's own
    spans: every ``obs.span`` reached while it runs (``tdt.engine.*``,
    ``tdt.batcher.*`` — docs/observability.md, "Spans in the device
    trace") lands in the host plane of the ``.xplane.pb`` itself, on the
    device ops' clock, with its counts as the event's stats.

    When the obs layer is armed (``config.obs``, ISSUE 9) the exit path
    additionally drops ``obs_trace.json`` — the span/wait-telemetry
    chrome trace — into the same directory, so XProf planes and host
    spans render as one timeline.
    """
    if not do_prof:
        yield None
        return
    path = os.path.join(log_dir, name or "trace")
    os.makedirs(path, exist_ok=True)
    jax.profiler.start_trace(path)
    try:
        yield path
    finally:
        jax.profiler.stop_trace()
        if merge_hosts and jax.process_count() > 1:
            _merge_host_traces(path, name or "trace")
        from triton_dist_tpu import obs as _obs

        _obs.maybe_export_into(path)


def _merge_host_traces(path: str, name: str) -> str | None:
    """Gather every process's newest profile-run files into ONE run dir on
    process 0: ``<path>/plugins/profile/<name>_merged/rank<r>_<file>``
    (collective — every process must call this; returns the merged dir on
    process 0, None elsewhere). File names keep their ``.xplane.pb`` /
    ``.json.gz`` suffixes so the profile viewer accepts the merged run;
    the rank prefix disambiguates same-hostname processes."""
    import glob
    import gzip
    import pickle

    from jax.experimental import multihost_utils

    runs = sorted(glob.glob(os.path.join(path, "plugins", "profile", "*")))
    runs = [r for r in runs if not r.endswith("_merged")]
    payload: list = []
    if runs:
        for f in sorted(glob.glob(os.path.join(runs[-1], "*"))):
            with open(f, "rb") as fh:
                payload.append((os.path.basename(f), fh.read()))
    # gzipped before the gather: process_allgather replicates
    # [nproc, max_blob] to EVERY host (the simple collective the
    # jax.distributed client offers), so the wire/memory cost is
    # nproc × the largest compressed blob — fine for the short profiled
    # regions this context manager wraps; profile a narrower region
    # rather than a whole run if traces grow to hundreds of MB.
    blob = np.frombuffer(gzip.compress(pickle.dumps(payload)), np.uint8)
    lens = multihost_utils.process_allgather(np.array([blob.size], np.int64))
    padded = np.zeros((int(lens.max()),), np.uint8)
    padded[: blob.size] = blob
    all_blobs = multihost_utils.process_allgather(padded)  # [nproc, maxlen]
    if jax.process_index() != 0:
        return None
    out_run = os.path.join(path, "plugins", "profile", f"{name}_merged")
    os.makedirs(out_run, exist_ok=True)
    for r in range(jax.process_count()):
        files = pickle.loads(
            gzip.decompress(all_blobs[r, : int(lens[r, 0])].tobytes())
        )
        for fname, content in files:
            with open(os.path.join(out_run, f"rank{r}_{fname}"), "wb") as fh:
                fh.write(content)
    return out_run


def bytes_of(x: jax.Array | jax.ShapeDtypeStruct) -> int:
    return int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize


@contextlib.contextmanager
def hang_watchdog(timeout_s: float = 300.0, *, dump: bool = True,
                  on_timeout: Callable[[], None] | None = None):
    """Failure detection for distributed programs (the reference has none —
    SURVEY.md §5: errors are fail-fast only, hangs just hang).

    A collective with a mismatched participant, a deadlocked semaphore, or
    a dead peer host leaves ``block_until_ready`` waiting forever with no
    diagnostics. Wrap the blocking region::

        with hang_watchdog(120):
            jax.block_until_ready(train_step(...))

    If the region is still running after `timeout_s`, the watchdog dumps
    every Python thread's stack to stderr (``dump=True``) and calls
    `on_timeout` if given — a hook for e.g. aborting the coordinator so
    the job fails loudly instead of burning a reservation. The watchdog is
    passive until the deadline and adds one daemon thread of overhead.
    """
    import faulthandler
    import sys
    import threading

    done = threading.Event()

    def watch():
        if done.wait(timeout_s):
            return
        suffix = " — dumping thread stacks" if dump else ""
        print(
            f"[hang_watchdog] region still blocked after {timeout_s:.0f}s"
            f"{suffix}",
            file=sys.stderr, flush=True,
        )
        if dump:
            faulthandler.dump_traceback(file=sys.stderr)
        if on_timeout is not None:
            on_timeout()

    t = threading.Thread(target=watch, daemon=True, name="tdt-hang-watchdog")
    t.start()
    try:
        yield
    finally:
        done.set()
        t.join(timeout=1.0)
