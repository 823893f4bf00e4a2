"""Observability layer (triton_dist_tpu/obs/, docs/observability.md;
ISSUE 9): host span tracing + device wait telemetry, exported as one
timeline.

Tier structure (mirrors tests/test_chunked.py):

- **host tier** (runs everywhere): span nesting/stats/ring bounds on a
  FakeClock, telemetry-buffer decode units, chrome-trace schema +
  byte-identical FakeClock exports, guard-ladder rung spans, jit
  trace-vs-cached spans, autotune policy spans, health drop attribution,
  ``group_profile`` run-dir return, serving-engine phase stats, and
  spans-armed-vs-disarmed bit-exactness through the golden op paths;
- **kernel tier** (needs the Mosaic TPU interpreter): wait_stats armed
  vs disarmed bit-exactness on the chunked ring pipeline, with real
  per-site spin telemetry decoded and aggregated;
- **chaos tier** (``pytest.mark.chaos``, runs in chaos_matrix.sh): an
  injected straggler (``FaultPlan``) shifts the victim wait sites' spin
  histograms — wait-cost attribution proven end to end.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from triton_dist_tpu import config as tdt_config
from triton_dist_tpu import obs
from triton_dist_tpu.obs import telemetry as T
from triton_dist_tpu.resilience import FaultPlan, guarded_call, health, retry
from triton_dist_tpu.resilience import records as R


TIMEOUT_ITERS = 300
DELAY_ITERS = 500


@pytest.fixture(autouse=True)
def _obs_isolation():
    """config.obs is process-global like the health registry: restore the
    disarmed default and clear the span ring + telemetry aggregation
    around every test (config snapshot includes the chaos knobs some
    cells arm)."""
    cfg = tdt_config.get_config()
    snap = (cfg.obs, cfg.timeout_iters, cfg.fault_plan,
            cfg.raise_on_timeout, cfg.fallback_to_xla)
    obs.reset()
    yield
    tdt_config.update(
        obs=snap[0], timeout_iters=snap[1], fault_plan=snap[2],
        raise_on_timeout=snap[3], fallback_to_xla=snap[4],
    )
    retry.set_clock(None)
    obs.reset()


def _arm(**kw):
    tdt_config.update(obs=obs.ObsConfig(**kw))


# ---------------------------------------------------------------------------
# Host tier: config + tracer
# ---------------------------------------------------------------------------

def test_obs_config_validation():
    with pytest.raises(ValueError):
        obs.ObsConfig(max_spans=0).validate()
    with pytest.raises(ValueError):
        tdt_config.update(obs="yes")
    # well-formed configs install and disarm cleanly
    _arm(wait_stats=True)
    assert obs.wait_stats_enabled()
    tdt_config.update(obs=None)
    assert not obs.wait_stats_enabled()
    assert not obs.span_enabled()


def test_disarmed_is_inert():
    with obs.span("never", cat="x") as sp:
        assert sp is obs.NULL_SPAN
        sp.set("rung", "fused")  # must be accepted and dropped
        obs.annotate(ignored=True)
    obs.record_span("never2", 0.0, 1.0)
    obs.instant("never3")
    assert obs.spans() == []
    assert obs.span_stats() == {}


def test_span_nesting_stats_on_fake_clock():
    _arm()
    with retry.clock_scope(retry.FakeClock()) as clock:
        with obs.span("outer", cat="op", a=1) as sp:
            clock.sleep(0.010)
            with obs.span("inner"):
                clock.sleep(0.002)
            sp.set("rung", "fused")
    spans = {s.name: s for s in obs.spans()}
    assert spans["outer"].depth == 0 and spans["inner"].depth == 1
    assert spans["outer"].attrs == {"a": 1, "rung": "fused"}
    assert spans["outer"].dur_ms == pytest.approx(12.0)
    assert spans["inner"].dur_ms == pytest.approx(2.0)
    st = obs.span_stats()
    assert st["outer"]["count"] == 1
    assert st["outer"]["total_ms"] == pytest.approx(12.0)
    # annotate targets the innermost OPEN span only
    with obs.span("open"):
        obs.annotate(tag="yes")
    assert [s for s in obs.spans() if s.name == "open"][0].attrs == {
        "tag": "yes"
    }


def test_span_ring_bound_counts_drops_stats_streaming():
    """No silent caps: ring evictions are counted, and the streaming
    per-name stats keep every sample regardless."""
    _arm(max_spans=4)
    with retry.clock_scope(retry.FakeClock()):
        for _ in range(10):
            with obs.span("s"):
                pass
    assert len(obs.spans()) == 4
    assert obs.dropped_spans() > 0
    assert obs.span_stats()["s"]["count"] == 10


# ---------------------------------------------------------------------------
# Host tier: telemetry decode + aggregation units
# ---------------------------------------------------------------------------

def _fake_row(family="fake_fam", pe=3, overflow=0, sites=()):
    code = R.family_code_for(family)
    row = np.zeros(T.TELEM_LEN, np.int32)
    row[T.H_FAMILY] = code
    row[T.H_PE] = pe
    row[T.H_OVERFLOW] = overflow
    for site, kind, calls, total, mx, bins in sites:
        base = T.TELEM_HEADER + site * T.TELEM_FIELDS
        row[base + T.T_KIND] = kind
        row[base + T.T_CALLS] = calls
        row[base + T.T_TOTAL] = total
        row[base + T.T_MAX] = mx
        for b, n in enumerate(bins):
            row[base + T.T_BINS + b] = n
    return row


def test_telem_layout_and_decode():
    assert T.TELEM_LEN == T.TELEM_HEADER + T.TELEM_SLOTS * T.TELEM_FIELDS
    bins = [0] * T.TELEM_BINS
    bins[T.spin_bin(9)] = 2
    row = _fake_row(sites=[
        (0, R.KIND_BARRIER, 2, 18, 9, bins),
        (5, R.KIND_CHUNK, 1, 0, 0, [1] + [0] * (T.TELEM_BINS - 1)),
    ], overflow=3)
    zero = np.zeros(T.TELEM_LEN, np.int32)  # padding row: no launches
    decoded = T.decode_telem(np.stack([row, zero]))
    assert len(decoded) == 1
    d = decoded[0]
    assert d["family"] == "fake_fam" and d["pe"] == 3
    assert d["overflow_sites"] == 3
    assert [s["site"] for s in d["sites"]] == [0, 5]
    s0 = d["sites"][0]
    assert s0["kind"] == "barrier_all"
    assert (s0["calls"], s0["total_spins"], s0["max_spins"]) == (2, 18, 9)
    assert s0["bins"][T.spin_bin(9)] == 2
    assert d["sites"][1]["kind"] == "chunk_wait"


def test_spin_bin_edges():
    # bin 0 = zero spins; log4 thereafter; last bin open-ended
    assert T.spin_bin(0) == 0
    assert T.spin_bin(1) == 1
    assert T.spin_bin(3) == 1
    assert T.spin_bin(4) == 2
    assert T.spin_bin(16) == 3
    assert T.spin_bin(10**9) == T.TELEM_BINS - 1
    assert len(T.BIN_EDGES) == T.TELEM_BINS + 1
    # the exported edges must MATCH the bin select: bin b covers
    # [BIN_EDGES[b], BIN_EDGES[b+1]) — these edges ship verbatim into
    # every trace artifact, so a misalignment mislabels every histogram
    for spins in (0, 1, 3, 4, 15, 16, 255, 4095, 4096, 10**9):
        b = T.spin_bin(spins)
        assert T.BIN_EDGES[b] <= spins < T.BIN_EDGES[b + 1], (spins, b)


def test_telem_aggregation_merges_and_surfaces_overflow():
    row = _fake_row(sites=[(1, R.KIND_SIGNAL, 1, 7, 7,
                            [0] * T.TELEM_BINS)], overflow=2)
    T.record_decoded(T.decode_telem(row))
    T.record_decoded(T.decode_telem(row))
    summary = T.wait_summary()
    assert summary["launches"] == 2
    assert summary["overflow_sites"] == {"fake_fam": 4}
    (site,) = [s for s in summary["sites"] if s["family"] == "fake_fam"]
    assert site["calls"] == 2 and site["total_spins"] == 14
    assert site["max_spins"] == 7 and site["mean_spins"] == 7.0
    assert site["kind"] == "signal_wait_until"
    json.dumps(summary)


def test_in_kernel_write_protocol_host_harness():
    """Drive ``watchdog._record_wait_telemetry`` with a numpy-backed fake
    SMEM ref and concrete jnp scalars — validating the slot arithmetic,
    the read-modify-write accumulation, the unrolled bin select, and the
    overflow header on every jax line (the live interpreter cells below
    are gated; this protocol check is not)."""
    from unittest import mock

    from triton_dist_tpu.resilience import watchdog as W

    class FakeRef:
        def __init__(self):
            self.buf = np.zeros(T.TELEM_LEN, np.int64)

        def __getitem__(self, i):
            return jnp.int32(int(self.buf[i]))

        def __setitem__(self, i, v):
            self.buf[i] = int(v)

    def fake_when(cond):  # pl.when with concrete bools
        def deco(fn):
            if bool(cond):
                fn()
            return fn

        return deco

    ref = FakeRef()
    scope = W.KernelDiagScope(None, "fake_kernel_w", telem_ref=ref)
    scope.pe = jnp.int32(1)
    with mock.patch("jax.experimental.pallas.when", fake_when):
        for spins in (0, 3, 17, 17):
            W._record_wait_telemetry(scope, 2, R.KIND_CHUNK,
                                     jnp.int32(spins))
        W._record_wait_telemetry(scope, T.TELEM_SLOTS + 5, R.KIND_WAIT,
                                 jnp.int32(9))
        # fast-fail chained waits (budget clamped to 0) must record
        # NOTHING — a zero-spin "call" would deflate the histograms
        W._record_wait_telemetry(scope, 2, R.KIND_CHUNK, jnp.int32(0),
                                 live=jnp.bool_(False))
        W._record_wait_telemetry(scope, T.TELEM_SLOTS + 6, R.KIND_WAIT,
                                 jnp.int32(0), live=jnp.bool_(False))
        # the spin accumulator saturates at INT32_MAX instead of wrapping
        # negative (heavy-stall regime under a large poll budget)
        W._record_wait_telemetry(scope, 3, R.KIND_SIGNAL,
                                 jnp.int32(2**31 - 10))
        W._record_wait_telemetry(scope, 3, R.KIND_SIGNAL, jnp.int32(100))
    ref.buf[T.H_FAMILY] = R.family_code_for("fake_kernel_w")
    (d,) = T.decode_telem(ref.buf.astype(np.int32))
    assert d["pe"] == 1 and d["overflow_sites"] == 1
    s, s3 = d["sites"]
    assert s["site"] == 2 and s["kind"] == "chunk_wait"
    assert (s["calls"], s["total_spins"], s["max_spins"]) == (4, 37, 17)
    expect = [0] * T.TELEM_BINS
    for sp in (0, 3, 17, 17):
        expect[T.spin_bin(sp)] += 1
    assert s["bins"] == expect
    assert s3["site"] == 3 and s3["total_spins"] == 2**31 - 1, s3


# ---------------------------------------------------------------------------
# Host tier: exporters
# ---------------------------------------------------------------------------

def _trace_program(clock):
    """One deterministic span+telemetry program (run under a FakeClock)."""
    with obs.span("op:fake", cat="op") as sp:
        clock.sleep(0.004)
        sp.set("rung", "fused")
    obs.record_span("serving:e2e", 0.5, 1.25, cat="serving",
                    track="req:r0", uid="r0")
    obs.instant("marker", note="hi")
    T.record_decoded(T.decode_telem(_fake_row(
        sites=[(0, R.KIND_CHUNK, 4, 40, 20,
                [0, 0, 1, 3] + [0] * (T.TELEM_BINS - 4))])))


def test_chrome_export_schema(tmp_path):
    _arm()
    with retry.clock_scope(retry.FakeClock()) as clock:
        _trace_program(clock)
    path = obs.export_chrome_trace(str(tmp_path / "obs.json"))
    doc = json.load(open(path))
    events = doc["traceEvents"]
    assert isinstance(events, list) and events
    for e in events:
        assert e["ph"] in ("M", "X", "i")
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert e["dur"] >= 0 and isinstance(e["ts"], (int, float))
        if e["ph"] == "i":
            assert e["s"] in ("t", "p", "g")
    # the acceptance artifact shape: op spans carry ladder rungs AND the
    # decoded per-site wait-spin histogram rides as telemetry instants
    ops = [e for e in events if e["ph"] == "X" and e["name"] == "op:fake"]
    assert ops and ops[0]["args"]["rung"] == "fused"
    assert ops[0]["dur"] == pytest.approx(4000.0)  # µs
    waits = [e for e in events if e.get("cat") == "wait_telemetry"
             and "spin_bins" in e.get("args", {})]
    assert waits and waits[0]["args"]["total_spins"] == 40
    assert sum(waits[0]["args"]["spin_bins"]) == 4
    # serving spans land on their own track lane
    e2e = [e for e in events if e["name"] == "serving:e2e"][0]
    assert e2e["dur"] == pytest.approx(750000.0)


def test_chrome_export_byte_identical_across_fakeclock_runs(tmp_path):
    _arm()
    blobs = []
    for i in range(2):
        obs.reset()
        with retry.clock_scope(retry.FakeClock()) as clock:
            _trace_program(clock)
        p = obs.export_chrome_trace(str(tmp_path / f"run{i}.json"))
        blobs.append(open(p, "rb").read())
    assert blobs[0] == blobs[1]


def test_chrome_export_merge_accumulates(tmp_path):
    _arm()
    path = str(tmp_path / "merged.json")
    with retry.clock_scope(retry.FakeClock()) as clock:
        with obs.span("a"):
            clock.sleep(0.001)
        obs.export_chrome_trace(path, merge=True, label="m1")
        n1 = len(json.load(open(path))["traceEvents"])
        obs.export_chrome_trace(path, merge=True, label="m2")
    events = json.load(open(path))["traceEvents"]
    assert len(events) > n1
    labels = {e["args"].get("label") for e in events if "args" in e}
    assert {"m1", "m2"} <= labels


def test_trace_summary_cli(tmp_path, capsys):
    _arm()
    with retry.clock_scope(retry.FakeClock()) as clock:
        _trace_program(clock)
    path = obs.export_chrome_trace(str(tmp_path / "obs.json"))
    import importlib.util
    import pathlib

    spec = importlib.util.spec_from_file_location(
        "trace_summary",
        pathlib.Path(__file__).resolve().parents[1]
        / "scripts" / "trace_summary.py",
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main([path, "-n", "5"]) == 0
    out = capsys.readouterr().out
    assert "wait site" in out and "slowest spans" in out
    assert "op:fake" in out and "chunk_wait" in out


def test_obs_snapshot_merges_surfaces():
    _arm()
    with retry.clock_scope(retry.FakeClock()):
        with obs.span("op:x"):
            pass
    health.record_downgrade("famx", "because")
    snap = obs.snapshot()
    # the always-present sections of the versioned schema (ISSUE 15:
    # flight-recorder sections appear only when their tier is armed)
    assert set(snap) == {"schema", "spans", "dropped_spans",
                         "wait_telemetry", "health", "serving"}
    assert snap["schema"] == obs.SNAPSHOT_SCHEMA
    assert "op:x" in snap["spans"]
    assert "famx:downgrade" in snap["health"]["counters"]
    json.dumps(snap)


# ---------------------------------------------------------------------------
# Host tier: guard / jit / autotune / retry wiring
# ---------------------------------------------------------------------------

def _rung_of(name):
    sp = [s for s in obs.spans() if s.name == f"op:{name}"]
    assert sp, [s.name for s in obs.spans()]
    return sp[-1].attrs.get("rung")


def test_guard_span_rung_fused():
    _arm()
    out = guarded_call("obs_fam_ok", lambda: 41 + 1, lambda: 0)
    assert out == 42
    assert _rung_of("obs_fam_ok") == "fused"


def test_guard_span_rung_golden_fallback():
    _arm()

    def primary():
        raise NotImplementedError("no Mosaic interpreter on this jax")

    out = guarded_call("obs_fam_fb", primary, lambda: "golden")
    assert out == "golden"
    sp = [s for s in obs.spans() if s.name == "op:obs_fam_fb"][-1]
    assert sp.attrs["rung"] == "golden_fallback"
    assert sp.attrs["cause"] == "NotImplementedError"


def test_guard_span_rung_golden_pinned():
    _arm()
    health.short_circuit("obs_fam_pin", "quarantined after watchdog timeout")
    out = guarded_call("obs_fam_pin", lambda: "fused", lambda: "golden")
    assert out == "golden"
    assert _rung_of("obs_fam_pin") == "golden_pinned"


def test_guard_span_rung_error_on_user_error():
    _arm()

    def primary():
        raise ValueError("bad shape")

    with pytest.raises(ValueError):
        guarded_call("obs_fam_err", primary, lambda: "golden")
    assert _rung_of("obs_fam_err") == "error"


def test_guard_disarmed_identical_results():
    """Spans armed vs disarmed must not change op results (host tier of
    the armed-is-observation-only contract; the kernel tier is below)."""
    x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
    run = lambda: guarded_call(  # noqa: E731
        "obs_fam_bits", lambda: jnp.sin(x) @ x, lambda: None
    )
    base = np.asarray(run())
    _arm()
    armed = np.asarray(run())
    assert np.array_equal(base, armed)
    assert _rung_of("obs_fam_bits") == "fused"


def test_jit_shard_map_span_trace_vs_cached(mesh8):
    import uuid

    from triton_dist_tpu.ops.common import jit_shard_map

    _arm()
    key = ("obs_jit_test", uuid.uuid4().hex)
    x = jnp.arange(32, dtype=jnp.float32).reshape(8, 4)

    def call():
        return jit_shard_map(
            lambda a: a * 2.0, mesh8, (P("tp"),), P("tp"), key=key
        )(x)

    np.testing.assert_array_equal(np.asarray(call()), np.asarray(x) * 2.0)
    call()
    jits = [s for s in obs.spans() if s.name == "jit:obs_jit_test"]
    assert [s.attrs["cached"] for s in jits] == [False, True]


def test_jit_wrapper_identity_and_late_arming(mesh8):
    """Unarmed entries with the same key must return the IDENTICAL
    callable (the test_elastic zero-overhead pin), AND a wrapper stored
    while obs was disarmed must start emitting jit spans once obs is
    armed mid-process — the per-call config discipline."""
    import uuid

    from triton_dist_tpu.ops.common import jit_shard_map

    key = ("obs_jit_late", uuid.uuid4().hex)
    x = jnp.arange(32, dtype=jnp.float32).reshape(8, 4)
    f1 = jit_shard_map(lambda a: a + 1.0, mesh8, (P("tp"),), P("tp"),
                       key=key)
    f2 = jit_shard_map(lambda a: a + 1.0, mesh8, (P("tp"),), P("tp"),
                       key=key)
    assert f1 is f2
    f1(x)  # disarmed: no spans
    assert [s for s in obs.spans() if s.name == "jit:obs_jit_late"] == []
    _arm()  # armed mid-process: the STORED wrapper picks it up
    f1(x)
    jits = [s for s in obs.spans() if s.name == "jit:obs_jit_late"]
    assert len(jits) == 1 and jits[0].attrs["cached"] is True


def test_stored_unarmed_wrapper_survives_later_watchdog_arming(mesh8):
    """A wrapper stored while the watchdog was DISARMED freezes its
    program at wrap time (the pre-obs contract): arming timeout_iters
    afterwards must neither change what the stored wrapper returns nor
    poison the program cache for a fresh armed entry with the same op
    key (the armed entry builds and caches its own diag-bearing
    program under a different config token)."""
    import uuid

    from triton_dist_tpu.ops.common import jit_shard_map

    key = ("obs_jit_poison", uuid.uuid4().hex)
    x = jnp.arange(32, dtype=jnp.float32).reshape(8, 4)
    stored = jit_shard_map(lambda a: a - 1.0, mesh8, (P("tp"),), P("tp"),
                           key=key)
    np.testing.assert_array_equal(np.asarray(stored(x)), np.asarray(x) - 1.0)
    tdt_config.update(timeout_iters=50)
    try:
        # the stored wrapper keeps serving its frozen unarmed program
        out = stored(x)
        assert not isinstance(out, tuple), "unarmed wrapper leaked diag"
        np.testing.assert_array_equal(np.asarray(out), np.asarray(x) - 1.0)
        # a FRESH entry under the armed config gets the armed program
        # (diag decoded host-side, clean run returns the bare output)
        armed = jit_shard_map(lambda a: a - 1.0, mesh8, (P("tp"),),
                              P("tp"), key=key)
        out2 = armed(x)
        np.testing.assert_array_equal(np.asarray(out2), np.asarray(x) - 1.0)
    finally:
        tdt_config.update(timeout_iters=0)


def test_autotune_policy_span_records_crowned():
    from triton_dist_tpu.autotuner import contextual_autotune

    _arm()

    @contextual_autotune([{"b": 1}, {"b": 2}], name="obs_tune_test")
    def op(x, config=None):
        return x * config["b"]

    assert op(3) == 3  # interpreter policy: first viable candidate
    inst = [s for s in obs.spans() if s.name == "autotune:obs_tune_test"]
    assert inst and inst[-1].attrs["policy"] == "interpreter"
    assert inst[-1].attrs["crowned"] == repr({"b": 1})


def test_retry_annotates_enclosing_span():
    from triton_dist_tpu.resilience.records import DistTimeoutError
    from triton_dist_tpu.resilience.retry import RetryPolicy, call_with_retry

    _arm()
    attempts = {"n": 0}

    def flaky():
        attempts["n"] += 1
        if attempts["n"] == 1:
            raise DistTimeoutError("obs_fam_retry", [])
        return "ok"

    with retry.clock_scope(retry.FakeClock()):
        with obs.span("op:obs_fam_retry", cat="op"):
            out = call_with_retry(
                "obs_fam_retry", flaky,
                policy=RetryPolicy(max_attempts=3, base_delay_s=0.0),
            )
    assert out == "ok"
    sp = [s for s in obs.spans() if s.name == "op:obs_fam_retry"][-1]
    assert sp.attrs["retries"] == 1
    assert sp.attrs["retry_class"] == "transient"


# ---------------------------------------------------------------------------
# Host tier: health drop attribution + group_profile satellites
# ---------------------------------------------------------------------------

def test_health_deque_drops_counted_and_attributed():
    """The bounded event deque past MAX_EVENTS evicts oldest-first — the
    evictions must be counted AND attributed by kind (no silent caps),
    while the per-(family, kind) counters never lose anything."""
    for _ in range(health.MAX_EVENTS + 40):
        health.record_downgrade("fam_drop", "spam")
    health.record_integrity("fam_rot")
    snap = health.snapshot()
    assert snap["dropped_events"] == 41
    assert snap["dropped_by_kind"] == {"downgrade": 41}
    assert snap["counters"]["fam_drop:downgrade"] == health.MAX_EVENTS + 40
    # the kind that mattered survived the storm in the counters either way
    assert snap["counters"]["fam_rot:integrity"] == 1
    health.reset()
    assert health.snapshot()["dropped_events"] == 0
    assert health.snapshot()["dropped_by_kind"] == {}


def test_group_profile_returns_run_dir_and_drops_obs_artifact(tmp_path):
    import os

    from triton_dist_tpu.utils import group_profile

    _arm()
    with retry.clock_scope(retry.FakeClock()) as clock:
        with obs.span("profiled"):
            clock.sleep(0.001)
    with group_profile("obs_run", log_dir=str(tmp_path)) as run_dir:
        jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
    assert run_dir == os.path.join(str(tmp_path), "obs_run")
    assert os.path.isdir(run_dir)
    # the obs chrome trace lands in the SAME run dir as the XProf planes
    obs_json = os.path.join(run_dir, "obs_trace.json")
    assert os.path.exists(obs_json)
    names = [e["name"] for e in json.load(open(obs_json))["traceEvents"]]
    assert "profiled" in names


def test_group_profile_do_prof_false_yields_none(tmp_path):
    from triton_dist_tpu.utils import group_profile

    with group_profile("x", do_prof=False, log_dir=str(tmp_path)) as p:
        assert p is None


# ---------------------------------------------------------------------------
# Engine tier: serving lifecycle spans
# ---------------------------------------------------------------------------

def test_serving_engine_phase_span_stats():
    from triton_dist_tpu.models import init_params
    from triton_dist_tpu.models.decode import Request
    from triton_dist_tpu.models.tp_transformer import TransformerConfig
    from triton_dist_tpu.ops.allgather_gemm import AGGemmConfig
    from triton_dist_tpu.ops.gemm_reduce_scatter import GemmRSConfig
    from triton_dist_tpu.serving import ServingConfig, ServingEngine

    _arm()
    cfg = TransformerConfig(
        vocab=32, hidden=32, ffn=64, n_layers=1, n_q_heads=4, n_kv_heads=2,
        head_dim=8, batch=2, seq=8,
        ag_config=AGGemmConfig(8, 16, 16), rs_config=GemmRSConfig(8, 16, 16),
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("tp",))
    clock = retry.FakeClock()
    eng = ServingEngine(cfg, params, mesh1, s_max=16, clock=clock,
                        serving=ServingConfig(virtual_step_s=0.01))
    for i, (p, o) in enumerate([(3, 4), (5, 3)]):
        eng.submit(Request(list(range(1, p + 1)), max_new_tokens=o,
                           uid=f"r{i}"))
    eng.run_until_idle()
    snap = eng.snapshot()
    # the satellite contract: per-phase p50/p99 from the tracer ride the
    # engine snapshot — a step-time breakdown, not just e2e percentiles
    sm = snap["span_ms"]
    for phase in ("serving:queued", "serving:prefill", "serving:decode",
                  "serving:e2e"):
        assert sm[phase]["count"] == 2, (phase, sm)
        assert sm[phase]["p99_ms"] >= 0.0
    # phases decompose e2e on the shared engine clock
    assert sm["serving:e2e"]["total_ms"] == pytest.approx(
        sm["serving:queued"]["total_ms"] + sm["serving:prefill"]["total_ms"]
        + sm["serving:decode"]["total_ms"], rel=1e-6)
    # per-request tracks render as parallel lanes in the export
    tracks = {s.track for s in obs.spans() if s.cat == "serving"}
    assert tracks == {"req:r0", "req:r1"}
    # and obs.snapshot() folds the live engine in (weak registration)
    osnap = obs.snapshot()
    assert osnap["serving"] is not None
    assert any(v["requests"]["finished"] == 2
               for v in osnap["serving"].values())


# ---------------------------------------------------------------------------
# Kernel tier (Mosaic interpreter): live wait telemetry
# ---------------------------------------------------------------------------

def _mesh2():
    return Mesh(np.array(jax.devices()[:2]), ("tp",))


def test_wait_stats_armed_bit_exact_and_attributed():
    """The acceptance contract: obs armed (wait_stats on top of the
    watchdog) is observation-only — results bit-exact to the fully
    disarmed run — while the decoded telemetry attributes every bounded
    wait site of the chunked ring pipeline."""
    from triton_dist_tpu.ops.allgather import all_gather_op

    mesh2 = _mesh2()
    x = jax.random.normal(jax.random.PRNGKey(7), (2 * 16, 4), jnp.float32)
    base = np.asarray(
        all_gather_op(x, mesh2, method="ring_1d", chunks_per_shard=2)
    )
    tdt_config.update(timeout_iters=10_000)
    _arm(wait_stats=True)
    armed = np.asarray(
        all_gather_op(x, mesh2, method="ring_1d", chunks_per_shard=2)
    )
    assert np.array_equal(base, armed), "armed obs must be observation-only"
    summary = T.wait_summary()
    assert summary["launches"] >= 2  # one telemetry row per PE
    kinds = {s["kind"] for s in summary["sites"]}
    assert "chunk_wait" in kinds, summary
    for s in summary["sites"]:
        assert s["calls"] >= 1
        assert sum(s["bins"]) == s["calls"]
        assert s["total_spins"] >= 0 and s["max_spins"] <= 10_000


def test_wait_stats_without_watchdog_is_inert():
    """wait_stats without timeout_iters must add nothing (the chunk
    signal discipline: no watchdog, no bounded waits, no telemetry)."""
    from triton_dist_tpu.ops.allgather import all_gather_op

    mesh2 = _mesh2()
    _arm(wait_stats=True)  # watchdog NOT armed
    x = jax.random.normal(jax.random.PRNGKey(8), (2 * 16, 4), jnp.float32)
    out = all_gather_op(x, mesh2, method="ring_1d", chunks_per_shard=2)
    assert np.isfinite(np.asarray(out)).all()
    assert T.wait_summary()["sites"] == []


@pytest.mark.chaos
def test_straggler_shifts_victim_wait_site_spin_histogram():
    """End-to-end attribution (the ISSUE 9 acceptance cell): a straggler
    PE injected via FaultPlan delays its entry into the chunked ring
    pipeline, so the OTHER PE's bounded waits for its chunks observe more
    spins — the per-site spin histograms must shift at the waits that
    block on the victim, and the clean-vs-straggler comparison names
    them."""
    from triton_dist_tpu.ops.allgather import all_gather_op

    mesh2 = _mesh2()
    x = jax.random.normal(jax.random.PRNGKey(9), (2 * 16, 4), jnp.float32)

    def run(plan):
        obs.reset()
        tdt_config.update(timeout_iters=50_000, fault_plan=plan,
                          raise_on_timeout=True)
        _arm(wait_stats=True)
        out = all_gather_op(x, mesh2, method="ring_1d", chunks_per_shard=2)
        return np.asarray(out), {
            (s["family"], s["site"], s["kind"]): s["total_spins"]
            for s in T.wait_summary()["sites"]
        }

    clean_out, clean = run(None)
    strag_out, strag = run(
        FaultPlan("straggler", pe=1, delay_iters=DELAY_ITERS)
    )
    # observation-only under chaos too: the straggler skews timing, never
    # values (the PR 1 contract) — and no watchdog trip at this budget
    np.testing.assert_allclose(strag_out, clean_out, rtol=1e-5, atol=1e-5)
    assert set(strag) == set(clean), "site sets must agree clean vs chaos"
    shifts = {k: strag[k] - clean[k] for k in strag}
    assert max(shifts.values()) > 0, (
        f"a {DELAY_ITERS}-iteration straggler must inflate some wait "
        f"site's observed spins; shifts={shifts}"
    )
    victim_site = max(shifts, key=lambda k: shifts[k])
    # the biggest shift must be a wait that can block on the straggler
    # (barrier entry or a chunk/signal wait), not an unrelated site
    assert victim_site[2] in ("barrier_all", "chunk_wait",
                              "signal_wait_until", "wait"), (
        victim_site, shifts,
    )
