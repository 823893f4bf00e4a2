"""Spans inside the serving loop (docs/observability.md, "Spans in the
device trace") and ``Finished.t_tokens``.

``obs.span`` is a ``jax.profiler.TraceAnnotation`` whenever a profiler
session runs, so the serving loop's ``tdt.*`` spans land in the
``.xplane.pb`` itself, beside the device ops. World-1 mesh, the tiny
one-block model, interpreted kernels; the engine clock is a FakeClock, the
profiler session is real."""

import glob
import time

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu import config as tdt_config
from triton_dist_tpu import obs
from triton_dist_tpu.models import init_params
from triton_dist_tpu.models.decode import Request
from triton_dist_tpu.models.tp_transformer import TransformerConfig
from triton_dist_tpu.ops.allgather_gemm import AGGemmConfig
from triton_dist_tpu.ops.gemm_reduce_scatter import GemmRSConfig
from triton_dist_tpu.resilience import retry
from triton_dist_tpu.serving import (
    Arrival,
    ServingConfig,
    ServingEngine,
    SpecDecodeConfig,
)
from triton_dist_tpu.serving.engine import Finished

# span -> (the span it lies under, the attributes it carries)
TABLE = {
    "tdt.engine.serve": (None, {"offered"}),
    "tdt.engine.ingest": ("tdt.engine.serve",
                          {"n", "late_us_sum", "late_us_max"}),
    "tdt.engine.sleep": ("tdt.engine.serve", {"dt_us"}),
    "tdt.engine.step": ("tdt.engine.serve", {"pending", "in_flight"}),
    "tdt.engine.admit": ("tdt.engine.step", {"admitted"}),
    "tdt.engine.observe": ("tdt.engine.step", {"first_tokens", "finished"}),
    "tdt.engine.rebuild": (None, {"reason", "replayed"}),
    "tdt.batcher.take_params": ("tdt.engine.rebuild", {
        "relaid", "bytes", "relaid_wqkv", "bytes_wqkv"}),
    "tdt.batcher.admit": ("tdt.engine.step", {"queued", "admitted"}),
    "tdt.batcher.admit_prefill": (
        "tdt.batcher.admit",
        {"uid", "slot", "prompt_len", "bucket", "admitted", "rows"}),
    "tdt.batcher.admit_prefill.build": ("tdt.batcher.admit_prefill", set()),
    "tdt.batcher.admit_prefill.dispatch": ("tdt.batcher.admit_prefill", set()),
    "tdt.batcher.admit_prefill.pull": ("tdt.batcher.admit_prefill", set()),
    "tdt.batcher.decode_round": (
        "tdt.engine.step", {"round", "live", "feeding", "tokens", "finished"}),
    "tdt.batcher.decode_round.upload": ("tdt.batcher.decode_round", set()),
    "tdt.batcher.decode_round.dispatch": ("tdt.batcher.decode_round", set()),
    "tdt.batcher.decode_round.pull": ("tdt.batcher.decode_round", set()),
    "tdt.batcher.decode_round.sample": ("tdt.batcher.decode_round", set()),
}
# attributes a span carries on some of its occurrences only: ``ahead`` = 1
# on a round that sent the next round's step before its own pull
SOMETIMES = {"tdt.batcher.decode_round": {"ahead"}}


@pytest.fixture(autouse=True)
def _isolation():
    cfg = tdt_config.get_config()
    before = cfg.obs
    obs.reset()
    yield
    tdt_config.update(obs=before)
    retry.set_clock(None)
    obs.reset()


@pytest.fixture(scope="module")
def mesh1() -> Mesh:
    return Mesh(np.array(jax.devices()[:1]), ("tp",))


@pytest.fixture(scope="module")
def tiny1():
    cfg = TransformerConfig(
        vocab=32, hidden=32, ffn=64, n_layers=1, n_q_heads=4, n_kv_heads=2,
        head_dim=8, batch=2, seq=8,
        ag_config=AGGemmConfig(8, 16, 16), rs_config=GemmRSConfig(8, 16, 16),
    )
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _engine(tiny1, mesh1, **kw):
    cfg, params = tiny1
    kw.setdefault("serving", ServingConfig(virtual_step_s=0.01))
    kw.setdefault("prefill", True)
    return ServingEngine(cfg, params, mesh1, s_max=16,
                         clock=retry.FakeClock(), **kw)


def _traffic(t0: float) -> list:
    """Two requests due at once (a backlog for the two slots), a third
    once they are done (the loop sleeps for it), a fourth that samples."""
    return [
        Arrival(t0, Request([1, 2, 3], 4, uid="a")),
        Arrival(t0, Request([4, 5, 6, 7, 8], 3, uid="b")),
        Arrival(t0 + 1.0, Request([9, 8, 7], 3, uid="c")),
        Arrival(t0 + 1.0, Request([2, 4, 6, 8], 3, uid="d",
                                  temperature=0.8, seed=11)),
    ]


class _Profiled:
    """A real profiler session; on exit ``spans`` holds the ``tdt.*``
    events of the host planes as dicts, nested by interval per thread."""

    def __init__(self, path):
        self.path, self.spans = str(path), []

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level, opts.host_tracer_level = 0, 1
        jax.profiler.start_trace(self.path, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        from jax.profiler import ProfileData

        jax.profiler.stop_trace()
        (pb,) = glob.glob(self.path + "/plugins/profile/*/*.xplane.pb")
        for plane in ProfileData.from_file(pb).planes:
            for line in plane.lines:
                evs = sorted(
                    (dict(name=e.name, t0=e.start_ns,
                          t1=e.start_ns + e.duration_ns, stats=dict(e.stats),
                          parent=None)
                     for e in line.events if e.name.startswith("tdt.")),
                    key=lambda s: (s["t0"], -s["t1"]))
                stack = []
                for s in evs:
                    while stack and stack[-1]["t1"] < s["t1"]:
                        stack.pop()
                    s["parent"] = stack[-1]["name"] if stack else None
                    stack.append(s)
                self.spans += evs

    def named(self, name):
        return [s for s in self.spans if s["name"] == name]


@pytest.fixture(scope="module")
def traced(tiny1, mesh1, tmp_path_factory):
    """One serve of ``_traffic`` under a profiler session, then a rebuild:
    ``(session, results)``."""
    eng = _engine(tiny1, mesh1)
    with _Profiled(tmp_path_factory.mktemp("xplane")) as prof:
        results = eng.serve(_traffic(eng.clock.monotonic()))
        eng._rebuild("test")
    return prof, results


# -- the spans in the xplane -------------------------------------------------

def test_xplane_holds_every_span_of_the_table_with_its_attributes(traced):
    prof, _ = traced
    seen = {s["name"] for s in prof.spans}
    assert seen == set(TABLE)
    for s in prof.spans:
        assert set(s["stats"]) - SOMETIMES.get(s["name"], set()) == (
            TABLE[s["name"]][1]), s
    (serve,) = prof.named("tdt.engine.serve")
    assert serve["stats"] == {"offered": 4}
    # "a" and "b" are due together and differ in bucket: a pass each; "c"
    # and "d" share theirs
    assert [(s["stats"]["uid"], s["stats"]["admitted"], s["stats"]["slot"])
            for s in prof.named("tdt.batcher.admit_prefill")] == [
                ("a", 1, 0), ("b", 1, 1), ("c|d", 2, 0)]
    rounds = [s["stats"]["round"] for s in
              prof.named("tdt.batcher.decode_round")]
    assert rounds == list(range(rounds[0], rounds[0] + len(rounds)))
    # "a" and "b" decode together, greedy and two tokens from their end:
    # that round looks ahead; no round beside a sampling slot does
    ahead = [s["stats"].get("ahead", 0) for s in
             prof.named("tdt.batcher.decode_round")]
    assert set(ahead) == {0, 1}
    (rebuild,) = prof.named("tdt.engine.rebuild")
    assert rebuild["stats"] == {"reason": "test", "replayed": 0}


def test_nesting_is_the_tables(traced):
    prof, _ = traced
    for s in prof.spans:
        assert s["parent"] == TABLE[s["name"]][0], s
    # only a round in which a slot samples pulls the full rows
    assert 0 < len(prof.named("tdt.batcher.decode_round.sample")) < len(
        prof.named("tdt.batcher.decode_round"))
    # one sleep: the loop had nothing due and nothing in flight once
    (sleep,) = prof.named("tdt.engine.sleep")
    assert 0 < sleep["stats"]["dt_us"] <= 1_000_000


def test_tokens_are_counted_where_they_are_made(traced):
    prof, results = traced
    made = sum(s["stats"]["tokens"] for s in
               prof.named("tdt.batcher.decode_round"))
    made += sum(s["stats"]["admitted"] for s in
                prof.named("tdt.batcher.admit_prefill"))
    assert made == sum(len(r.tokens) for r in results.values()) == 13
    ended = sum(s["stats"]["finished"] for s in
                prof.named("tdt.batcher.decode_round"))
    assert ended == len(results) == 4
    seen = prof.named("tdt.engine.observe")
    assert sum(s["stats"]["first_tokens"] for s in seen) == 4
    assert sum(s["stats"]["finished"] for s in seen) == 4
    admits = prof.named("tdt.batcher.admit")
    assert sum(s["stats"]["admitted"] for s in admits) == 4
    assert all(s["stats"]["live"] <= 2 for s in
               prof.named("tdt.batcher.decode_round"))


def test_lateness_is_never_negative_and_zero_for_a_backlog(traced, tiny1,
                                                           mesh1, tmp_path):
    prof, _ = traced
    ingests = prof.named("tdt.engine.ingest")
    assert sum(s["stats"]["n"] for s in ingests) == 4
    for s in ingests:
        assert 0 <= s["stats"]["late_us_max"] <= s["stats"]["late_us_sum"]
    # a backlog at t=0 under a FakeClock is popped the moment it is due
    eng = _engine(tiny1, mesh1)
    t0 = eng.clock.monotonic()
    backlog = [Arrival(t0, Request([1 + i, 2, 3], 2, uid=f"q{i}"))
               for i in range(5)]
    with _Profiled(tmp_path) as again:
        eng.serve(backlog)
    (ingest,) = again.named("tdt.engine.ingest")
    assert ingest["stats"] == {"n": 5, "late_us_sum": 0, "late_us_max": 0}
    assert not again.named("tdt.engine.sleep")


# -- the ring: one system, on the engine clock -------------------------------

def test_disarmed_ring_stays_empty_and_tokens_do_not_move(traced, tiny1, mesh1):
    _, with_session = traced
    assert tdt_config.get_config().obs is None
    eng = _engine(tiny1, mesh1)
    plain = eng.serve(_traffic(eng.clock.monotonic()))
    assert obs.spans() == [] and obs.span_stats() == {}
    assert {u: r.tokens for u, r in plain.items()} == {
        u: r.tokens for u, r in with_session.items()}


def test_armed_ring_holds_the_spans_and_exports_byte_identically(
        tiny1, mesh1, tmp_path):
    tdt_config.update(obs=obs.ObsConfig())
    blobs = []
    for i in range(2):
        obs.reset()
        with retry.clock_scope(retry.FakeClock()):
            cfg, params = tiny1
            eng = ServingEngine(cfg, params, mesh1, s_max=16, prefill=True,
                                serving=ServingConfig(virtual_step_s=0.01))
            eng.serve(_traffic(eng.clock.monotonic()))
        path = obs.export_chrome_trace(str(tmp_path / f"run{i}.json"))
        blobs.append(open(path, "rb").read())
    assert blobs[0] == blobs[1]
    ring = {s.name: s for s in obs.spans()}
    assert set(TABLE) - {"tdt.engine.rebuild"} <= set(ring)
    # ... beside the lifecycle phases, on the same (engine) clock
    assert "serving:e2e" in ring
    assert ring["tdt.batcher.decode_round"].attrs["tokens"] >= 1
    assert ring["tdt.engine.sleep"].dur_ms == pytest.approx(
        ring["tdt.engine.sleep"].attrs["dt_us"] / 1e3, abs=1e-3)
    # a step costs its virtual 10 ms on the FakeClock, inside its span
    assert obs.span_stats()["tdt.engine.step"]["max_ms"] >= 10.0
    assert ring["tdt.batcher.decode_round.pull"].depth == (
        ring["tdt.batcher.decode_round"].depth + 1)


def test_a_span_with_nothing_listening_is_cheap():
    """A loose, steady guard (the budget is 2 us a span, a decode round
    opens four): no session, ``config.obs`` disarmed."""
    n = 20_000
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for i in range(n):
            with obs.span("tdt.batcher.decode_round", round=i) as sp:
                sp.set("tokens", 1)
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 5e-6, f"{best * 1e6:.2f} us a span"
    assert obs.spans() == []


# -- the other admission and decode paths ------------------------------------

def test_speculative_round_opens_the_round_span(tiny1, mesh1, tmp_path):
    cfg, params = tiny1
    sd = SpecDecodeConfig(draft_cfg=cfg, draft_params=params, k=3)
    eng = _engine(tiny1, mesh1, prefill=False,
                  serving=ServingConfig(virtual_step_s=0.01, speculative=sd))
    t0 = eng.clock.monotonic()
    with _Profiled(tmp_path) as prof:
        results = eng.serve([Arrival(t0, Request([1, 2, 3], 6, uid="s"))])
    rounds = prof.named("tdt.batcher.decode_round")
    spec = [s for s in rounds if s["stats"].get("kind") == "spec"]
    assert spec and len(spec) < len(rounds)   # the prompt is fed by plain rounds
    for s in spec:
        assert set(s["stats"]) == {"round", "kind", "live", "offered",
                                   "accepted", "tokens", "finished"}
        assert 0 <= s["stats"]["accepted"] <= s["stats"]["offered"]
        assert s["parent"] == "tdt.engine.step"
    assert sum(s["stats"]["tokens"] for s in rounds) == len(results["s"].tokens)
    assert [s["stats"]["round"] for s in rounds] == list(
        range(1, len(rounds) + 1))
    # a self-draft accepts what it offers: more than one token a round
    assert max(s["stats"]["tokens"] for s in spec) > 1


def test_ranged_admission_carries_its_span(tiny1, mesh1, tmp_path):
    eng = _engine(tiny1, mesh1, serving=ServingConfig(
        virtual_step_s=0.01, prefill_chunk_tokens=3))
    t0 = eng.clock.monotonic()
    with _Profiled(tmp_path) as prof:
        results = eng.serve(
            [Arrival(t0, Request([1, 2, 3, 4, 5, 6, 7, 8], 3, uid="long"))])
    passes = prof.named("tdt.batcher.ranged_pass")
    assert [(s["stats"]["lo"], s["stats"]["hi"], s["stats"]["bucket"])
            for s in passes] == [(0, 3, 4), (3, 6, 4), (6, 8, 2)]
    for s in passes:
        assert s["stats"]["uid"] == "long" and s["stats"]["slot"] == 0
        assert s["parent"] == "tdt.engine.step"
    assert not prof.named("tdt.batcher.admit_prefill")
    # the last chunk yields the first token, the rounds the rest; while the
    # chunks land the slot is live and feeding
    rounds = prof.named("tdt.batcher.decode_round")
    assert 1 + sum(s["stats"]["tokens"] for s in rounds) == len(
        results["long"].tokens) == 3
    assert rounds[0]["stats"]["feeding"] == rounds[0]["stats"]["live"] == 1


def test_a_pass_a_bucket_and_its_span_counts_its_members(tiny1, mesh1):
    """Eight queued requests of two buckets on eight free slots are two
    passes, in the order of the buckets' first members; the spans'
    ``admitted`` sum to the admissions, ``prompt_len`` is the longest
    member's, ``slot`` the first one's, and the work counter sweeps a
    rectangle a PASS."""
    import dataclasses

    from triton_dist_tpu.models.decode import ContinuousBatcher

    cfg, params = tiny1
    b = ContinuousBatcher(dataclasses.replace(cfg, batch=8), params, mesh1,
                          s_max=16, prefill=True, page_size=8)
    lens = [5, 3, 4, 7, 6, 3, 8, 4]
    rng = np.random.default_rng(5)
    for i, n in enumerate(lens):
        b.submit(Request([int(t) for t in rng.integers(0, cfg.vocab, n)], 3,
                         uid=i))
    tdt_config.update(obs=obs.ObsConfig(spans=True))
    b._admit()
    ring = obs.spans()
    passes = [s.attrs for s in ring if s.name == "tdt.batcher.admit_prefill"]
    (admit,) = [s.attrs for s in ring if s.name == "tdt.batcher.admit"]
    assert [(p["bucket"], p["admitted"], p["prompt_len"], p["slot"], p["uid"])
            for p in passes] == [(8, 4, 8, 0, "0|3|4|6"), (4, 4, 4, 1, "1|2|5|7")]
    assert admit["admitted"] == sum(p["admitted"] for p in passes) == 8
    assert b.prefill_passes_total == 2 and not b.queue
    assert b.prefill_tokens_total == sum(lens)
    assert b.prefill_work_total == 8 * 8 + 4 * 4
    assert b.pos.tolist() == lens and all(len(o) == 1 for o in b.slot_out)
    assert len(dict(b.run(max_steps=50))) == 8


# -- Finished.t_tokens -------------------------------------------------------

def _check_stamps(fin: Finished) -> None:
    assert isinstance(fin, Finished)
    assert len(fin.t_tokens) == len(fin.tokens)
    assert list(fin.t_tokens) == sorted(fin.t_tokens)
    assert fin.t_tokens[-1] == fin.t_finished


def test_every_token_has_the_time_the_engine_saw_it(traced):
    _, results = traced
    for fin in results.values():
        _check_stamps(fin)
        assert fin.t_tokens[0] == fin.t_first_token
        # one stamp a step, 10 ms of virtual time apart; the token the
        # admission's prefill made is seen only after the decode round of
        # the same step, so the first two share a stamp
        gaps = np.diff(fin.t_tokens)
        assert gaps[0] == 0.0 and np.allclose(gaps[1:], 0.01)
    assert Finished("u", [1], 0.0, 0.0, 0.1, 0.1, 0).t_tokens == ()


def test_a_speculative_rounds_tokens_share_a_stamp(tiny1, mesh1):
    cfg, params = tiny1
    sd = SpecDecodeConfig(draft_cfg=cfg, draft_params=params, k=3)
    eng = _engine(tiny1, mesh1, prefill=False,
                  serving=ServingConfig(virtual_step_s=0.01, speculative=sd))
    eng.submit(Request([1, 2, 3], 6, uid="s"))
    fin = eng.run_until_idle()["s"]
    _check_stamps(fin)
    assert fin.t_tokens[0] == fin.t_first_token
    assert len(set(fin.t_tokens)) < len(fin.t_tokens)


def test_stamps_are_kept_across_a_rebuild(tiny1, mesh1):
    eng = _engine(tiny1, mesh1)
    eng.submit(Request([1, 2, 3], 6, uid="r"))
    for _ in range(3):
        assert eng._step_once()
    seen = list(eng._states["r"].t_tokens)
    assert len(seen) == 4        # the prefill's token + one a round
    eng._rebuild("test")
    fin = eng.run_until_idle()["r"]
    _check_stamps(fin)
    assert fin.resumed == 1 and len(fin.tokens) == 6
    # the replayed prefix keeps the stamps it had; `t_first_token` is, as
    # before, the first token AFTER the replay
    assert list(fin.t_tokens[:4]) == seen
    assert fin.t_tokens[4] == fin.t_first_token > seen[-1]


# -- the frames under the jitted calls ---------------------------------------

def test_frames_under_the_jitted_calls_keep_their_summed_size():
    """A tripwire, not a law. ``setup_s`` on the chip is chaotic in the
    summed size of the Python frames between ``serve`` and the jitted
    prefill / decode calls: JAX walks every op of every Mosaic kernel with a
    Python callback, and where that callback's frame falls on a 16 KB
    boundary of CPython's frame stack each call maps and unmaps a chunk
    (+45 s of set-up in the chat cell; PERF.md section 6, PR 26). These
    sums were measured clean in all three cells. Changing one is allowed:
    measure ``setup_s`` on the chip in every cell, then update the numbers."""
    from triton_dist_tpu.models.decode import ContinuousBatcher as B

    def slots(f):
        c = f.__code__
        return (len(c.co_varnames) + len(c.co_cellvars) + len(c.co_freevars)
                + c.co_stacksize)

    common = [ServingEngine.serve, ServingEngine._step_once, B.step]
    assert sum(map(slots, common + [B._admit, B._admit_prefill])) == 76
    assert sum(map(slots, common + [B._decode_round])) == 62
