"""Test harness: 8 virtual CPU devices + TPU interpreter for all Pallas
kernels (SURVEY.md §4 — this is where we exceed the reference, which can
only test on real multi-GPU hardware).

NOTE: on hosts with very few CPU cores, XLA:CPU's host thread pool can
deadlock when many interpreted remote DMAs move large payloads concurrently
(observed threshold ~16 KiB/chunk in 8-device ring kernels on a 1-core
box). Keep per-DMA test payloads <= ~8 KiB; correctness coverage does not
need more, and real-TPU runs are unaffected.

Runtime budget: the driver runs tier-1 (`-m 'not slow'`) with six xdist
workers and `--dist loadfile` inside 1470 s, so the wall time is at least
the slowest FILE, and a run cut by the limit counts only as far as it got
(the driver's run of PR 43's tree was: 918 of 980 counted, which fails any
PR by the floor). What a cell costs is interpreted STEPS where devices
talk: every interpreted pallas_call pays ~44 ms of host machinery
(io_callbacks plus per-call shared-memory setup across virtual devices),
so a decode step of a two-layer model on the 4-device mesh is seconds (a
third of that on two devices), while a program's trace and compile are
about a tenth of the test that builds it. On ONE device (the plan
families' toys) it is the reverse: a round is 0.3-1 s and every distinct
program (the step, a prefill bucket, a forward at one length, a
configuration changed in one field) is 5-10 s of tracing, lowering and
compiling, so such a file costs the programs it builds
(callback-bearing executables are not cacheable on disk; the keyed
`jit_shard_map` cache shares them inside a process). The record, each the
driver's command on its builder's machine: PR 30 brought the suite from
1257 s to 763-871 s (4115-4522 test-seconds summed); PR 43's tree took
1221 s (6787 summed; three family files at 388-466 s each); PR 44 brought
it to 784 s (4371 summed; 992 passed; heaviest tests/test_chip_compile.py
at 287 s, then test_flash_decode.py 212, test_sparse_mla_moe.py 161,
test_emitter.py 159: the three that stand over a fifth of the NEW wall
time and are the next to split). One run reads 15-20% off another on a
shared machine: compare two trees in one hour. The rules:

- one geometry a file: the tests of a file share one `cfg` / mesh /
  `s_max` through module-scoped fixtures, so each program is built once,
  and a reference run that several tests compare against (a token-fed
  serve, a whole-range pass, a golden arc, a green campaign) is a
  module-scoped fixture too, run once;
- the smallest layer count and the shortest answers that still cover the
  property: two layers where a cache bit has to come out of attention, one
  where tokens are compared; answers just long enough to cross a decode
  round onto the second PE's rows; and the smallest MESH that has the
  property: a cell that compares tokens between admission forms, or a
  campaign's streams with a clean run's, needs a second PE, not four
  (`mesh2` below, `SoakSpec(world=2)`); four stay where the
  property is theirs (the ranged model tier, a shrink that must skip the
  mesh of three, a chain that spans PEs);
- the smallest campaign that still fires every fault it asserts on
  (`resilience/soak.py` fails a campaign whose scheduled fault never
  fired, so a campaign cut too far fails loudly, not vacuously); a replay
  cell reruns the green cell's spec, not a third campaign; the long sets
  live under `-m soak`;
- a plan family's tests are a `Family` descriptor for
  tests/family_tier.py plus what only that family has (its kernels against
  their twins, its plan and parameter counts): the tier holds the fixtures,
  the cases every family shows and their SIZE (tests/test_docs_refs.py
  fails a file that loads a program of perfbench/programs beside it). A new
  family file may cost 120 s as one of six processes: count its programs;
- no file over a fifth of the run's wall time: split it along its tiers
  (or, where the tier is one parametrised test, a file a cell:
  ranged_model_tier.py), and keep `_LONG_POLES` in the order of the last
  run's record. tests/test_chip_compile.py is the one file that cannot be
  split (its docstring says why) and stands first there."""

import os
import signal

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import Mesh  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: second-tier tests (models, tutorials, large shapes, "
        "multi-process) — excluded from the fast `-m quick` CI tier",
    )
    config.addinivalue_line(
        "markers",
        "quick: first-tier kernel-family coverage; `pytest -m quick` is "
        "the fast gate (~8 min on a 1-core box)",
    )
    config.addinivalue_line(
        "markers",
        "chaos: resilience-layer fault injection (tests/test_chaos.py). "
        "Fast interpret-mode cases ride tier-1 automatically; the full "
        "drop/dup/delay/straggler × kernel-family matrix is additionally "
        "marked slow — run it standalone via scripts/chaos_matrix.sh",
    )
    config.addinivalue_line(
        "markers",
        "soak: long seeded multi-fault chaos campaigns "
        "(tests/test_overload.py / resilience/soak.py, ISSUE 11). "
        "Automatically wired slow so tier-1 stays fast; run via "
        "scripts/chaos_soak.py or `pytest -m soak`",
    )


# Heaviest files first: every file over 1/160 of the summed test-seconds,
# longest first, from the `--junitxml` of PR 44's run of the driver's
# command on its own tree (six workers; the docstring above has its sums).
# Under `--dist loadfile` xdist hands whole files to workers in collection
# order, i.e. alphabetically: a multi-minute file that sorts late starts
# late and becomes the wall time. And a worker holds its NEXT file while it
# runs one, which no idle worker can take from it, so the run ends a file
# after its sum says: what runs last has to be small (hence 1/160, ~27 s,
# where 1/80 left files of a minute to the alphabet's end and a tail of
# ~100 s behind an evenly spread sum). Everything not named keeps its
# order. Re-read the order from a run's `--junitxml` when a file grows
# (tests/test_docs_refs.py holds the names to files that exist).
_LONG_POLES = (
    # one file by its own docstring (one process loads libtpu), +3-4
    # compiles a PR: first while it is the heaviest
    "test_chip_compile.py",
    # PR 47's run of the driver's command: 343 s with the walked-pass
    # cases (second of the record; its three mesh-of-four cases are 148 s of it)
    "test_lookahead.py",
    # PR 45's own reading, alone on this machine: 196 s (five planted
    # faults of two fresh programs each are 65 s of it); 341 s in PR 47's
    "test_retention.py",
    # PR 48's own reading, alone on this machine: 170 s since the family's
    # second plan (Mamba-2 over experts) runs the tier beside the first
    "test_ssm_hybrid.py",
    "test_flash_decode.py", "test_emitter.py", "test_disagg.py",
    "test_sparse_mla_moe.py",
    "test_window_moe.py", "test_prerouted_moe.py",
    "test_ranged_prefill.py", "test_disagg_soak.py", "test_serving.py",
    "test_ranged_contiguous.py", "test_ranged_kernel.py",
    "test_ragged_pipeline.py", "test_integrity.py",
    "test_overload.py", "test_moe_pipeline.py", "test_fp8.py",
    "test_ranged_paged.py", "test_recovery.py",
    "test_spec_serving.py", "test_mla_moe.py", "test_gate_up_layout.py",
    "test_ring_attention.py", "test_spec_soak.py", "test_gemm_rs.py",
    "test_chip_smoke.py", "test_recovery_soak.py", "test_ragged.py",
    "test_chunked.py", "test_chunked_prefill.py", "test_fleet.py",
    "test_ranged_batcher.py", "test_prefill_work.py",
    "test_prefix_cache.py", "test_flight_recorder.py",
    "test_chunked_a2a.py", "test_moe.py", "test_overlap_structure.py",
    "test_comm_jitter.py", "test_prefix_cache_chaos.py", "test_ag_gemm.py",
    "test_gemm_tiles.py", "test_races.py", "test_reduce_scatter.py",
    "test_prefix_cache_soak.py", "test_dcn.py", "test_live_prefix.py",
    "test_allgather.py",
)


def pytest_collection_modifyitems(config, items):
    rank = {name: i for i, name in enumerate(_LONG_POLES)}
    items.sort(key=lambda it: rank.get(it.path.name, len(rank)))  # stable
    for item in items:
        # soak implies slow (ISSUE 11): the campaign tier never rides the
        # fast gate, and forgetting the second marker can't break that
        if "soak" in item.keywords and "slow" not in item.keywords:
            item.add_marker(pytest.mark.slow)
        # quick == everything not explicitly marked slow, so the quick
        # tier can't silently lose new tests
        if "slow" not in item.keywords and "soak" not in item.keywords:
            item.add_marker(pytest.mark.quick)


def _cell_alarm(item, phase):
    """Per-cell wall-clock budget (ISSUE 11 satellite): with
    ``TDT_CELL_TIMEOUT_S`` set (scripts/chaos_matrix.sh exports it), a
    SIGALRM fires a TimeoutError inside the hung cell, so it reports as
    one named FAILED/ERROR row instead of stalling the whole matrix.
    Armed around ALL THREE phases (setup / call / teardown — a fixture
    can hang just as hard as a test body). Signal delivery needs the
    main thread + a Python bytecode boundary — true for every
    interpret-mode cell here; a cell wedged inside a C call fails at its
    next return to Python."""
    import contextlib

    @contextlib.contextmanager
    def scope():
        budget = float(os.environ.get("TDT_CELL_TIMEOUT_S", "0") or 0)
        if budget <= 0 or not hasattr(signal, "SIGALRM"):
            yield
            return

        def _alarm(signum, frame):
            raise TimeoutError(
                f"cell {phase} exceeded TDT_CELL_TIMEOUT_S={budget:g}s: "
                f"{item.nodeid}"
            )

        old = signal.signal(signal.SIGALRM, _alarm)
        signal.setitimer(signal.ITIMER_REAL, budget)
        try:
            yield
        finally:
            signal.setitimer(signal.ITIMER_REAL, 0)
            signal.signal(signal.SIGALRM, old)

    return scope()


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_setup(item):
    with _cell_alarm(item, "setup"):
        yield


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    with _cell_alarm(item, "call"):
        yield


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_teardown(item):
    with _cell_alarm(item, "teardown"):
        yield


@pytest.fixture(scope="session", autouse=True)
def _interpret_mode():
    from triton_dist_tpu import config

    config.update(interpret=True)
    yield


@pytest.fixture
def chip_posture():
    """The program a chip runs, traced here: ``config.interpreting()``
    false for one test (no validating ``lax.cond``, and so no sort of its
    own, around the routed combine)."""
    from triton_dist_tpu import config

    posture = config.get_config().interpret
    config.update(interpret=False)
    yield
    config.update(interpret=posture)


@pytest.fixture(autouse=True)
def _resilience_isolation():
    """The resilience health registry is process-global: a watchdog
    quarantine or downgrade recorded by one test would pin later tests'
    op entries to the golden path, silently changing what they cover.
    Reset around every test."""
    from triton_dist_tpu import resilience
    from triton_dist_tpu.obs import alerts, blackbox, metrics

    def _flight_recorder_reset():
        # the ISSUE 15 flight-recorder registries are process-global like
        # the health registry: series/alerts/bundle census recorded by an
        # armed test must not leak into the next one (the tracer ring and
        # telemetry aggregation stay: test_obs manages those explicitly)
        metrics.reset()
        alerts.reset()
        blackbox.reset()

    resilience.reset()
    _flight_recorder_reset()
    yield
    resilience.reset()
    _flight_recorder_reset()


@pytest.fixture(scope="session")
def mesh8() -> Mesh:
    return Mesh(np.array(jax.devices()), ("tp",))


@pytest.fixture(scope="session")
def mesh2x4() -> Mesh:
    return Mesh(np.array(jax.devices()).reshape(2, 4), ("dp", "tp"))


@pytest.fixture(scope="session")
def mesh4() -> Mesh:
    return Mesh(np.array(jax.devices()[:4]), ("tp",))


@pytest.fixture(scope="session")
def mesh2() -> Mesh:
    return Mesh(np.array(jax.devices()[:2]), ("tp",))


@pytest.fixture(scope="session")
def mesh2x2x2() -> Mesh:
    return Mesh(np.array(jax.devices()).reshape(2, 2, 2), ("a", "b", "c"))
