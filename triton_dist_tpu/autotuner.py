"""Contextual autotuner for distributed kernels
(≙ reference ``python/triton_dist/autotuner.py``, 256 LoC:
``contextual_autotune(is_dist=True)(fn)``).

The reference wraps Triton's autotuner so that *the whole distributed op*
(not just one kernel) is timed per config, aggregates timings across ranks
(a config must be fastest for the slowest rank), and logs decisions to
``.autotune_logs/rank-N.log``.

TPU-native form: time the whole jitted thunk per candidate config with
``perf_func``; under SPMD one process drives all local devices, so the
cross-rank aggregation the reference needs (NCCL all-reduce of timings)
reduces to the walltime of the slowest device — which walltime already is.
Multi-host runs gather every process's per-config timings
(``multihost_utils.process_allgather``) and pick the config minimizing the
MAX over processes — the reference's slowest-rank rule (autotuner.py:97):
on DCN-attached heterogeneous topologies rank 0's local winner can be a
straggler's worst case. A config that failed on ANY process is
disqualified everywhere, and rank 0's (identical, deterministic) pick is
still broadcast as the authoritative tie-break so all processes apply the
same config or collectives would deadlock.

Decisions persist to ``.autotune_cache/<name>.json`` keyed by the call
signature, so production runs pay zero tuning cost.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import math
import os
from typing import Any, Callable, Iterable, Sequence

import jax

from triton_dist_tpu import config as tdt_config
from triton_dist_tpu.resilience import DistTimeoutError
from triton_dist_tpu.utils import perf_func_loop, perf_pair_loop


# tuned winners live in the checkout (git-ignored), not in whatever the
# working directory happens to be
_CACHE_DIR = os.environ.get(
    "TDT_AUTOTUNE_CACHE",
    os.path.join(tdt_config.CHECKOUT, ".autotune_cache"),
)
_memory_cache: dict[tuple[str, str], Any] = {}


def _sig_key(args: Sequence[Any], kwargs: dict[str, Any]) -> str:
    """Shape/dtype signature of the call (config-independent)."""
    parts = []
    for a in jax.tree.leaves((args, kwargs)):
        if hasattr(a, "shape") and hasattr(a, "dtype"):
            parts.append(f"{a.dtype}{list(a.shape)}")
        elif isinstance(a, (int, float, str, bool)) or a is None:
            parts.append(repr(a))
        else:
            # non-array context (Mesh, method enums, …) must key the cache
            # too: distinct contexts with identical array shapes are
            # different tuning problems. Long strings keep a readable
            # prefix plus a hash of the FULL text — a bare truncation let
            # two contexts sharing a 160-char prefix collide and silently
            # serve each other's cached config
            s = str(a)
            if len(s) > 160:
                digest = hashlib.sha256(s.encode("utf-8", "replace")).hexdigest()[:16]
                s = f"{s[:120]}#{digest}"
            parts.append(s)
    try:
        parts.append(f"dev={jax.devices()[0].device_kind}x{len(jax.devices())}")
    except Exception:
        pass
    return ";".join(parts)


def _cache_path(name: str) -> str:
    return os.path.join(_CACHE_DIR, f"{name}.json")


def _load_disk_cache(name: str) -> dict[str, Any]:
    try:
        with open(_cache_path(name)) as f:
            return json.load(f)
    except Exception:
        return {}


def _store_disk_cache(name: str, table: dict[str, Any]) -> None:
    """Atomic merge-write: re-read the table first (another process may have
    tuned other signatures meanwhile), then temp-file + os.replace so a crash
    mid-write can never leave a truncated/corrupt cache."""
    try:
        os.makedirs(_CACHE_DIR, exist_ok=True)
        merged = _load_disk_cache(name)
        merged.update(table)
        table.update(merged)
        tmp = _cache_path(name) + f".tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(merged, f, indent=1, sort_keys=True)
        os.replace(tmp, _cache_path(name))
    except Exception:
        pass


@dataclasses.dataclass
class AutotuneResult:
    config: Any
    times_ms: list[float]


def _slowest_rank_best(all_times, margin: float = 0.02) -> int:
    """Min-max cross-rank aggregation (≙ reference ``autotuner.py:97``):
    given ``[n_proc, n_cfg]`` per-process timings, pick the config whose
    SLOWEST process is fastest. ``inf`` anywhere disqualifies the config
    everywhere (it failed on that rank — applying it would desync the
    fleet). The same order-preference walk as the local sweep applies: a
    later candidate must beat the current leader's worst-case time by
    `margin` to displace it, so spaces' best-known leaders keep their seat
    against cross-host timing noise. Returns -1 when every config failed
    somewhere (caller falls back to its local pick)."""
    import numpy as np

    worst = np.max(np.asarray(all_times, np.float64), axis=0)
    finite = np.isfinite(worst)
    if not finite.any():
        return -1
    leader = int(np.argmax(finite))   # first config finite on every rank
    best = leader
    for i in range(leader + 1, worst.size):
        if finite[i] and worst[i] < worst[best] * (1.0 - margin):
            best = i
    return best


def contextual_autotune(
    configs: Iterable[Any],
    *,
    name: str | None = None,
    iters: int = 60,
    trials: int = 3,
    dedupe: Callable[..., Any] | None = None,
    precondition: Callable[..., bool] | None = None,
    sweep_in_interpret: bool = False,
) -> Callable:
    """Decorator: sweep `configs` for the wrapped op on first call per input
    signature, thereafter reuse the winner (≙ ``contextual_autotune``,
    reference autotuner.py:97).

    The wrapped function must accept a ``config=`` keyword. Candidates that
    fail to compile/run are skipped (the reference likewise discards configs
    that raise, autotuner.py:150-170).

    Each candidate is scored by the median of `trials` on-device loop
    timings (``perf_func_loop`` — one compile per config; per-call host
    walltime is too noisy to rank configs, and iters=15
    windows were still jitter-bound at ms-scale ops: a measured window
    ≳300 ms per sample is what makes candidate ranking trustworthy).

    Under the TPU *interpreter* (CPU tests) timings are meaningless and a
    sweep costs minutes per signature, so the first viable candidate is
    used directly unless ``sweep_in_interpret=True`` (set by the
    autotuner's own unit tests).

    `dedupe`, if given, maps ``(cfg, *args, **kwargs)`` to the config's
    EFFECTIVE key for this problem (e.g. the clamped block shape); configs
    that collapse to the same key are timed once and share the result.

    `precondition`, if given, maps ``(cfg, *args, **kwargs)`` to whether
    the candidate is SENSIBLE for this problem — a shape-aware guard for
    the sweep-free paths (cached_or_first / interpreter), where the walk
    applies the first surviving candidate untimed: a config that is
    best-known at the bench shape can be pathological elsewhere (e.g. a
    512-row MoE alignment block padding a 16-token problem 100×). Filtered
    configs are skipped by sweeps too; if the filter rejects every
    candidate it is ignored outright (never an error). Must be
    deterministic in its arguments — multi-host relies on every process
    walking the same candidate order.
    """
    configs = list(configs)

    def deco(fn: Callable) -> Callable:
        op_name = name or fn.__name__
        disk = _load_disk_cache(op_name)

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            if "config" in kwargs and kwargs["config"] is not None:
                return fn(*args, **kwargs)
            kwargs.pop("config", None)
            key = _sig_key(args, kwargs)
            mem_key = (op_name, key)
            if mem_key in _memory_cache:
                return fn(*args, config=_memory_cache[mem_key], **kwargs)
            # disk entries store {"i": index, "cfg": repr} — the repr guards
            # against a reordered/edited candidate list silently applying
            # the wrong config. Multi-host skips the disk fast path: an
            # asymmetric cache hit would leave one host sweeping (and
            # joining collectives) alone — all hosts sweep, rank 0 decides.
            entry = disk.get(key) if jax.process_count() == 1 else None
            if (
                isinstance(entry, dict)
                and 0 <= entry.get("i", -1) < len(configs)
                and entry.get("cfg") == repr(configs[entry["i"]])
            ):
                _memory_cache[mem_key] = configs[entry["i"]]
                return fn(*args, config=_memory_cache[mem_key], **kwargs)

            # shape-aware candidate filter (see docstring); a filter that
            # rejects everything (or raises) is ignored, never fatal
            cands = configs
            if precondition is not None:
                try:
                    ok = [
                        cfg for cfg in configs
                        if precondition(cfg, *args, **kwargs)
                    ]
                except Exception:
                    ok = []
                if ok:
                    cands = ok

            def _first_viable(reason: str):
                """Apply the first candidate that runs — NEVER a sweep.
                Skips are always logged to stderr: demoting the best-known
                config on a transient error must not look like a genuine
                perf regression. Memory-cache only: the disk cache real
                tuned runs consult is never written by these paths."""
                import sys

                last_err: Exception | None = None
                for cfg in cands:
                    try:
                        out = fn(*args, config=cfg, **kwargs)
                    except DistTimeoutError:
                        # a watchdog trip is a peer-loss event, not a
                        # candidate-viability problem: retrying per config
                        # would burn one full timeout budget per candidate
                        # and mask a sick fleet as "all configs failed"
                        raise
                    except Exception as e:
                        last_err = e
                        print(
                            f"[autotune {op_name}] {reason}: candidate "
                            f"{cfg!r} failed ({e!r:.200}); trying next",
                            file=sys.stderr, flush=True,
                        )
                        continue
                    _memory_cache[mem_key] = cfg
                    # obs (ISSUE 9): the sweep-free walks crown a config
                    # too — record it so a timeline reader can tell an
                    # untimed policy pick from a measured sweep winner
                    from triton_dist_tpu import obs as _obs

                    _obs.instant(
                        f"autotune:{op_name}", cat="autotune",
                        policy=reason, crowned=repr(cfg),
                    )
                    return out
                raise RuntimeError(
                    f"autotune({op_name}): every candidate config failed "
                    f"({reason})"
                ) from last_err

            # TDT_AUTOTUNE_POLICY=cached_or_first: signature cache hit
            # (handled above) or the first VIABLE candidate. This is the
            # bounded-time mode for runs inside a budgeted window (the
            # driver bench): a sweep costs a compile + timed loop per
            # candidate. Tune spaces therefore lead with their best-known
            # config. Multi-host intentionally ignores even a warm disk
            # cache here (per-host cache files can diverge and a
            # mismatched config choice deadlocks collectives): every
            # process deterministically walks the same candidate order
            # without coordination.
            if os.environ.get("TDT_AUTOTUNE_POLICY") == "cached_or_first":
                return _first_viable("cached_or_first")

            if tdt_config.interpreting() and not sweep_in_interpret:
                # interpreter timings are noise
                return _first_viable("interpreter")

            from triton_dist_tpu import obs as _obs
            from triton_dist_tpu.resilience import retry as _retry

            sweep_t0 = _retry.get_clock().monotonic()
            times = [float("inf")] * len(configs)
            seen: dict[Any, int] = {}
            for i, cfg in enumerate(configs):
                if cfg not in cands:
                    continue  # filtered by the precondition: never timed
                if dedupe is not None:
                    try:
                        eff = dedupe(cfg, *args, **kwargs)
                    except Exception:
                        eff = i
                    if eff in seen:
                        times[i] = times[seen[eff]]  # same effective kernel
                        continue
                    seen[eff] = i
                try:
                    # consume="all": tune spaces mix side-effectful Pallas
                    # candidates with pure XLA-native sentinels; a partial
                    # consumption lets DCE shrink the pure ones to a slice
                    # and they'd "win" every sweep regardless of true speed
                    times[i] = perf_func_loop(
                        functools.partial(fn, config=cfg, **kwargs),
                        args,
                        iters=iters,
                        trials=trials,
                        consume="all",
                    )
                except DistTimeoutError:
                    raise  # peer loss, not a config problem (see above)
                except Exception as e:  # config doesn't fit this problem
                    if tdt_config.get_config().verbose_autotune:
                        print(f"[autotune {op_name}] cfg {cfg} failed: {e!r}")
            if not any(t != float("inf") for t in times):
                raise RuntimeError(
                    f"autotune({op_name}): every candidate config failed"
                )
            # Order-preference walk: spaces LEAD with the best-known /
            # XLA-native-sentinel config, and sweep timings are unpaired
            # samples with a few-% noise floor — so a later candidate must
            # beat the current leader by a real margin to displace it.
            # Without this, ±2% jitter regularly crowns a marginally
            # slower kernel over the sentinel and the bench's paired
            # ratio then reads 0.98 instead of 1.00.
            margin = 0.02
            leader = next(
                i for i in range(len(configs)) if times[i] != float("inf")
            )
            best_i = leader
            for i in range(best_i + 1, len(configs)):
                if times[i] < times[best_i] * (1.0 - margin):
                    best_i = i
            if best_i != leader and jax.process_count() == 1:
                # A displacement measured from unpaired sweep samples can
                # still be jitter (r3 chip evidence: a Pallas config beat
                # the world-1 XLA sentinel in the sweep, then LOST the
                # bench's paired loop 0.998:1). Confirm with the same
                # interleaved paired timing the bench trusts; the leader
                # keeps its seat unless the challenger wins it paired.
                # (Multi-host skips this: the confirm pass would need every
                # rank to join both loops in lockstep — the slowest-rank
                # aggregation below decides from the gathered sweep
                # timings instead.)
                try:
                    _, _, ratio = perf_pair_loop(
                        functools.partial(fn, config=configs[best_i], **kwargs),
                        functools.partial(fn, config=configs[leader], **kwargs),
                        args, iters=iters, rounds=3,
                    )
                    # ratio = t_leader / t_challenger
                    if ratio < 1.0 + margin:
                        best_i = leader
                except Exception:
                    best_i = leader  # confirm failed: trust the order bias
            best_t = times[best_i]
            if jax.process_count() > 1:
                # slowest-rank aggregation (≙ the reference's cross-rank
                # rule, autotuner.py:97): gather every process's timings
                # and pick the config minimizing the max over ranks — on
                # heterogeneous (DCN-attached) topologies rank 0's local
                # winner can be another rank's straggler. Every process
                # computes the same min-max pick from the same gathered
                # matrix; rank 0's broadcast remains the authoritative
                # tie-break (all processes must apply the same config or
                # collectives mismatch).
                from jax.experimental import multihost_utils
                import numpy as _np

                all_times = multihost_utils.process_allgather(
                    _np.asarray(times, _np.float64)
                )
                agg = _slowest_rank_best(all_times, margin)
                if agg >= 0:
                    best_i = agg
                best_i = int(
                    multihost_utils.broadcast_one_to_all(_np.int32(best_i))
                )
                # the logged timing below is THIS RANK'S local sample of
                # the fleet's choice — it can be inf when the config
                # failed here (harmless: the disk cache stores the index)
                best_t = times[best_i]
            if tdt_config.get_config().verbose_autotune:
                t_str = f"{best_t:.3f} ms" if math.isfinite(best_t) else (
                    "n/a locally"  # rank 0's pick; this rank's sample failed
                )
                print(
                    f"[autotune {op_name}] {key} -> {configs[best_i]} "
                    f"({t_str}; all={['%.3f' % t for t in times]})"
                )
            # obs (ISSUE 9): the candidate sweep + crowned config as one
            # span — who was timed, what won, and what the sweep cost
            _obs.record_span(
                f"autotune:{op_name}", sweep_t0,
                _retry.get_clock().monotonic(), cat="autotune",
                track="autotune", n_candidates=len(configs),
                n_timed=sum(1 for t in times if t != float("inf")),
                crowned=repr(configs[best_i]),
                best_ms=(round(best_t, 6) if math.isfinite(best_t)
                         else "inf"),
            )
            _memory_cache[mem_key] = configs[best_i]
            disk[key] = {"i": best_i, "cfg": repr(configs[best_i])}
            _store_disk_cache(op_name, disk)
            return fn(*args, config=configs[best_i], **kwargs)

        wrapped.autotune_configs = configs
        return wrapped

    return deco
