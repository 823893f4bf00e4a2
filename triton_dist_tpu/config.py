"""Global configuration for triton_dist_tpu.

The single most important switch is *interpret mode*: every distributed
Pallas kernel in this framework runs either compiled via Mosaic (on real TPU)
or under the TPU interpreter (``pltpu.InterpretParams``) which simulates
remote DMAs, semaphores and multi-core timing on CPU — including an optional
happens-before race detector (``detect_races=True``).

This replaces the reference's noise-injection "race shaking"
(Triton-distributed ``allgather.py:72-76``) with a real race detector, and is
what lets the full SPMD test-suite run on an
``--xla_force_host_platform_device_count=8`` virtual mesh.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any

import jax


@dataclasses.dataclass
class Config:
    # None = auto: interpret on non-TPU backends, compiled on TPU.
    interpret: bool | None = None
    # Enable the TPU interpreter's happens-before race detector.
    detect_races: bool = False
    # 'on_wait' mimics real DMA async semantics; 'eager' is faster.
    dma_execution_mode: str = "on_wait"
    # Fail loudly when EP dispatch drops assignments to slab overflow
    # (≙ the reference's assert, low_latency_all_to_all.py:212): prints a
    # host-side diagnostic AND NaN-poisons the layer output so an
    # undersized max_m can never silently zero expert contributions in a
    # training run (see also layers.ep_moe_mlp.assert_no_overflow for a
    # host-side hard stop on the fetched counter).
    debug_ep_overflow: bool = False
    # Print autotuner decisions.
    verbose_autotune: bool = bool(int(os.environ.get("TDT_VERBOSE_AUTOTUNE", "0")))
    # Hardware race shaking (≙ the reference's random comm-stream sleeps,
    # allgather.py:72-76): > 0 inserts a per-PE pseudo-random busy delay
    # of roughly this many VPU loop iterations at the top of every fused
    # comm kernel, skewing issue timing so arrival-order and
    # barrier-aliasing assumptions get exercised under timing variance
    # the interpreter's happens-before detector cannot model (its
    # schedule is data-dependency-driven, not time-driven). Debug/stress
    # only — tpu_smoke.py runs a delayed pass on real chips; keep 0 in
    # production. Env: TDT_COMM_DELAY.
    debug_comm_delay: int = int(os.environ.get("TDT_COMM_DELAY", "0"))
    # USER-DECLARED mesh axes whose hops cross TPU slice boundaries
    # (Multislice DCN, not ICI). Remote-DMA kernels cannot reach across
    # slices, so collective ops lower these axes to XLA collectives
    # (which ride DCN) and keep the fused kernels on the ICI axes. Real
    # Multislice meshes are AUTO-detected separately (scoped per mesh:
    # ``topology.register_mesh_dcn``, called by ``make_mesh``); declare
    # here only for virtual meshes / tests (≙ the reference treating its
    # inter-node plane differently from NVLink, allgather.py:291-375).
    # Ops consult ``topology.is_dcn_axis_name`` = declared ∪ detected.
    dcn_axes: tuple = ()
    # --- resilience subsystem (docs/resilience.md) ---------------------
    # Watchdog budget for every distributed wait (signal_wait_until /
    # wait / barrier_all rounds), in POLL ITERATIONS, not wall time:
    # > 0 arms bounded waits that, on expiry, write a structured
    # diagnostic record into the kernel's diag buffer, NaN-poison the
    # output, and surface host-side as resilience.DistTimeoutError.
    # 0 (default) keeps the classic blocking waits — zero overhead, no
    # extra kernel outputs. Calibrate per deployment: a compiled poll
    # iteration is tens of ns; an interpret-mode iteration costs a host
    # callback (chaos tests use small budgets). Env: TDT_TIMEOUT_ITERS.
    timeout_iters: int = int(os.environ.get("TDT_TIMEOUT_ITERS", "0"))
    # On a watchdog trip: True raises DistTimeoutError from the op entry
    # (serving code sees a loud, decodable failure); False returns the
    # fully NaN-poisoned output instead and only records the event in
    # resilience.health (for pipelines that prefer poison-and-continue).
    raise_on_timeout: bool = True
    # Armed resilience.FaultPlan (interpret-mode signal chaos: drop /
    # duplicate / delay a signal op, straggle a PE) — see
    # resilience/faults.py and tests/test_chaos.py. None = no injection.
    fault_plan: object = None
    # Graceful degradation: let resilience.guarded_call serve the golden
    # jax.lax collective path when a fused op fails for environmental
    # reasons (Mosaic compile failure, unsupported topology), recording
    # the downgrade in resilience.health — the SERVING posture, and the
    # default. False = every failure is loud: the posture of CI and of
    # everything that measures (chip_smoke.py, perfbench/, scripts/).
    # Env: TDT_FALLBACK_TO_XLA.
    fallback_to_xla: bool = bool(int(os.environ.get("TDT_FALLBACK_TO_XLA", "1")))
    # --- elastic degraded mode (docs/resilience.md) --------------------
    # Armed resilience.RetryPolicy: watchdog-armed op entries retry
    # TRANSIENT failures (DistTimeoutError — comm jitter, one lost
    # signal) with deterministic exponential backoff before escalating;
    # deterministic failures (compile/shape/API) are never retried and
    # keep going straight to the golden-path guard. None (default)
    # disables retry entirely — op entries take the pre-existing
    # single-attempt path with zero added per-step work.
    retry_policy: object = None
    # PE quarantine + topology shrink (resilience/elastic.py): attribute
    # watchdog timeouts to a straggler peer, quarantine it after
    # suspect_threshold strikes, rebuild collectives over the survivors
    # (elastic.effective_mesh), probe with a cheap barrier and re-admit
    # after probation_probes clean probes. False (default) = every
    # elastic entry point is a no-op and effective_mesh is identity.
    elastic: bool = False
    # Timeouts attributed to one peer before it is quarantined (the
    # first strike only marks it suspect; clean steps decay strikes).
    suspect_threshold: int = 2
    # Clean world-barrier probes required to re-admit a quarantined PE.
    probation_probes: int = 1
    # --- data-integrity layer (ISSUE 8, docs/resilience.md) ------------
    # Armed resilience.IntegrityConfig: host-tier output guards (finite
    # check + optional magnitude envelope) at every guarded op entry, the
    # serving engine's per-request NaN-logit quarantine, and — with
    # canary=True on top of an armed watchdog — per-chunk payload
    # checksums riding the chunked puts' existing signal slots. Detection
    # is observation-only on the happy path (clean runs stay bit-exact);
    # a tripped check raises resilience.IntegrityError and runs the
    # recovery ladder (retry → golden fallback → PE strikes). None
    # (default) = no checks, zero added work anywhere.
    integrity: object = None
    # --- observability layer (ISSUE 9, docs/observability.md) ----------
    # Armed obs.ObsConfig: host-side span tracing (guarded op entries
    # with their ladder rung, jit trace-vs-cached dispatch, autotune
    # sweeps, serving lifecycle) on the injectable resilience clock, and
    # — with wait_stats=True on top of an armed watchdog — a per-kernel
    # wait-telemetry buffer recording every bounded wait site's observed
    # spin count (success path included; rides the diag-output plumbing,
    # NO new signal edges). Exported via obs.export_chrome_trace() /
    # obs.snapshot(). None (default) = no spans,
    # zero new kernel outputs, bit-exact op results.
    obs: object = None


_config = Config()


def get_config() -> Config:
    return _config


def update(**kwargs: Any) -> None:
    for k, v in kwargs.items():
        if not hasattr(_config, k):
            raise ValueError(f"unknown config key: {k}")
        if k == "fault_plan":
            from triton_dist_tpu.resilience import faults as _faults

            if v is not None:
                if not isinstance(v, _faults.FaultPlan):
                    raise ValueError(
                        f"fault_plan must be a resilience.FaultPlan (or None), "
                        f"got {type(v).__name__}"
                    )
                v.validate()
            # a (re)armed plan starts with a full trigger budget
            _faults.reset_triggers()
        if k == "integrity" and v is not None:
            from triton_dist_tpu.resilience.integrity import IntegrityConfig

            if not isinstance(v, IntegrityConfig):
                raise ValueError(
                    f"integrity must be a resilience.IntegrityConfig (or "
                    f"None), got {type(v).__name__}"
                )
            v.validate()
        if k == "obs" and v is not None:
            from triton_dist_tpu.obs import ObsConfig

            if not isinstance(v, ObsConfig):
                raise ValueError(
                    f"obs must be an obs.ObsConfig (or None), got "
                    f"{type(v).__name__}"
                )
            v.validate()
        if k == "retry_policy" and v is not None:
            from triton_dist_tpu.resilience.retry import RetryPolicy

            if not isinstance(v, RetryPolicy):
                raise ValueError(
                    f"retry_policy must be a resilience.RetryPolicy (or "
                    f"None), got {type(v).__name__}"
                )
            v.validate()
        if k in ("suspect_threshold", "probation_probes") and int(v) < 1:
            raise ValueError(f"{k} must be >= 1, got {v}")
        setattr(_config, k, v)


def interpreting() -> bool:
    """Whether distributed kernels currently resolve to interpret mode
    (the debug/validation posture: CPU tests, dry runs)."""
    cfg = get_config()
    return cfg.interpret if cfg.interpret is not None else not on_tpu()


def on_tpu() -> bool:
    # a backend that fails to initialise raises here: a chip that cannot
    # be reached must never read as "no chip, interpret instead"
    return jax.default_backend() == "tpu"


CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def compile_cache_dir() -> str:
    """The one place JAX's persistent compile cache is placed. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
    is set in code; otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache`` (the path is part of the cache key, so it
    must not move between runs). Returns the directory in effect."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


_interp_scheduler_patched = False


def _patch_interpreter_scheduler() -> None:
    """De-starve the TPU interpreter's semaphore scheduler on low-core hosts.

    jax 0.9.0's interpreter executes pending DMAs lazily from within
    ``Semaphore.wait`` (``dma_execution_mode='on_wait'``); when a core waits
    on a semaphore whose producing DMA has not been *issued* yet (because the
    producing core is still in compute), the wait busy-spins on the shared
    lock. On a 1-core host the spinners starve the producing thread — a
    livelock for any kernel whose cross-device dependency chain passes
    through compute (exactly what fused GEMM+comm kernels do). This installs
    a copy of ``Semaphore.wait`` whose empty-task-queue branch sleeps briefly
    instead of hot-looping. Interpreter-only; never active on real TPU.
    """
    global _interp_scheduler_patched
    if _interp_scheduler_patched:
        return
    _interp_scheduler_patched = True
    try:
        # The body below is a copy of jax 0.9.0 internals (the pinned
        # install, pyproject.toml) with one changed branch.
        import time as _time

        _debug_wait = bool(int(os.environ.get("TDT_DEBUG_WAIT", "0")))

        from jax._src.pallas.mosaic.interpret import shared_memory as _sm
        from jax._src.pallas.mosaic.interpret import vector_clock as _vc

        def _wait(self, value, global_core_id, *, has_tasks=False):
            global_core_id = int(global_core_id)
            clock = None
            if not has_tasks:
                with self.cv:
                    while self.count_by_core[global_core_id] < value:
                        self.cv.wait()
                    self.count_by_core[global_core_id] -= value
                    if self.detect_races:
                        clock = _vc.copy_vector_clock(self.clocks[global_core_id])
                if self.detect_races:
                    with self.shared_memory.lock:
                        _vc.update_vector_clock(
                            self.shared_memory.clocks[global_core_id], clock
                        )
                return
            while True:
                clock = None
                with self.cv:
                    if self.count_by_core[global_core_id] >= value:
                        self.count_by_core[global_core_id] -= value
                        if self.detect_races:
                            clock = _vc.copy_vector_clock(self.clocks[global_core_id])
                        else:
                            return
                if clock is not None:
                    with self.shared_memory.lock:
                        _vc.update_vector_clock(
                            self.shared_memory.clocks[global_core_id], clock
                        )
                    return
                with self.shared_memory.lock:
                    task_queue = self.shared_memory.tasks_by_sem[
                        (self.id, global_core_id)
                    ]
                    task = task_queue.pop() if len(task_queue) > 0 else None
                if task is None:
                    _time.sleep(5e-4)  # the one change vs upstream: no hot spin
                    stalls = getattr(self, "_tdt_stalls", 0) + 1
                    self._tdt_stalls = stalls
                    if _debug_wait and stalls % 2000 == 0:
                        print(
                            f"[tdt-wait] sem={self.id} core={global_core_id} "
                            f"want={value} have={self.count_by_core[global_core_id]} "
                            f"stalls={stalls}",
                            flush=True,
                        )
                    continue
                self._tdt_stalls = 0
                task()

        _sm.Semaphore.wait = _wait
    except Exception as e:  # pragma: no cover - jax version drift
        import warnings

        warnings.warn(
            f"triton_dist_tpu: could not patch the Pallas interpreter "
            f"semaphore scheduler ({e!r}); interpreted distributed kernels "
            f"whose dependency chains pass through compute may livelock on "
            f"low-core hosts",
            RuntimeWarning,
        )


_cpu_tpu_info_registered = False


def _ensure_cpu_tpu_info() -> None:
    """Teach Pallas's TPU-info query about the CPU interpreter.

    ``pltpu.emit_pipeline`` asks for the current device's TPU generation to
    pick tilings; on the CPU backend that lookup fails. The module exposes a
    ``registry`` extension point for unknown device kinds — we register a
    v5e-lookalike for ``"cpu"`` so interpreted kernels tile like a real TPU.
    """
    global _cpu_tpu_info_registered
    if _cpu_tpu_info_registered:
        return
    try:
        from jax._src.pallas.mosaic import tpu_info

        def _cpu_info():
            return tpu_info.TpuInfo(
                chip_version=tpu_info.ChipVersion.TPU_V5E,
                generation=5,
                num_cores=1,
                num_lanes=128,
                num_sublanes=8,
                mxu_column_size=128,
                vmem_capacity_bytes=128 * 1024 * 1024,
                cmem_capacity_bytes=0,
                smem_capacity_bytes=1024 * 1024,
                hbm_capacity_bytes=17_200_000_000,
                mem_bw_bytes_per_second=int(8.20e11),
                bf16_ops_per_second=int(1.97e14),
                int8_ops_per_second=int(3.94e14),
                # v5e runs fp8_e4m3 at the int8 MXU rate (2x bf16); a 0
                # here would make any fp8 roofline silently infinite
                fp8_ops_per_second=int(3.94e14),
                int4_ops_per_second=int(7.88e14),
            )

        tpu_info.registry.setdefault("cpu", _cpu_info)
    except Exception:
        pass
    _cpu_tpu_info_registered = True


def interpret_params():
    """Resolve the `interpret=` argument for pallas_call.

    Returns False (compiled) on TPU backends, or a ``pltpu.InterpretParams``
    configured from the global config elsewhere (CPU tests, dry runs).
    """
    from jax.experimental.pallas import tpu as pltpu

    cfg = get_config()
    if not interpreting():
        return False
    _ensure_cpu_tpu_info()
    _patch_interpreter_scheduler()
    dma_mode = cfg.dma_execution_mode
    if cfg.timeout_iters > 0 or cfg.fault_plan is not None:
        # Watchdogged waits POLL semaphores (semaphore_read) instead of
        # blocking; under 'on_wait' the interpreter only executes pending
        # DMAs from inside Semaphore.wait, so a poll-only consumer would
        # starve its producers and every wait would time out spuriously.
        # Chaos/watchdog runs therefore force eager DMA execution.
        dma_mode = "eager"
    return pltpu.InterpretParams(
        detect_races=cfg.detect_races,
        dma_execution_mode=dma_mode,
        # Distributed kernels intentionally read buffers that are filled by
        # remote DMAs; OOB reads stay fatal but uninit memory must be lax.
        uninitialized_memory="zero",
        out_of_bounds_reads="raise",
    )
