"""Self-checks of the reader of the program's own spans
(``harness/spans.py``) and of the eight per-layer metrics built on it, on
a small hand-made host plane (``fixtures/spans_small.json``: one serve
call of 1000 us with an ingest, an admission of two requests, four decode
rounds, an idle poll, a sleep and a ranged admission; plus a warm-up
round before the call, another thread's round and a foreign name, none of
which may count), where every expected number was worked out by hand;
and on a 1.2 s cut of a real traced run (``fixtures/spans_recorded.json``,
spans and the device's busy intervals)."""

import os

import pytest

import run as bench_run
from harness import cells, spans as sp, trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixtures", "spans_small.json")
US = 1000
# what the device ran, in us: two prefills, four decode steps, a ranged pass
BUSY_US = [(125, 195), (204, 262), (276, 380), (410, 505), (529, 603),
           (915, 966), (975, 1080)]
NEW = ("engine.ingest_late_ms", "engine.step_self_ms", "batcher.round_host_ms",
       "batcher.round_host_max_ms", "batcher.round_pull_ms",
       "batcher.prefill_host_ms", "batcher.tokens_per_round",
       "device.idle_with_work_share")


@pytest.fixture
def spans():
    return sp.load_json(FIXTURE)


def device_trace():
    ops = {"names": ["fusion.1"], "idx": [0] * len(BUSY_US),
           "start": [a * US for a, _ in BUSY_US],
           "dur": [(b - a) * US for a, b in BUSY_US]}
    return tr.Trace.from_json({"/device:TPU:0": {tr.OPS: ops}})


def a_run(spans):
    b = cells.benchmark()
    return bench_run.Run(
        cell=cells.Cell(b, "mistral-7b-v0.3.chat"), trace=device_trace(),
        plane="/device:TPU:0", window=(99 * US, 1101 * US), tdt_spans=spans)


def test_only_the_serve_calls_own_spans_are_kept(spans):
    assert spans.serve.stats == {"offered": 3}
    assert {s.thread for s in spans.all} == {"/host:CPU/main"}
    assert all(s.name.startswith("tdt.") for s in spans.all)
    rounds = spans.named(sp.ROUND)
    assert [s.stats["round"] for s in rounds] == [1, 2, 3, 4]
    assert len(spans.named(sp.STEP)) == 5 and len(spans.named(sp.SLEEP)) == 1


def test_self_time_by_nesting(spans):
    step1, step2 = spans.named(sp.STEP)[:2]
    assert [c.name for c in step1.children] == [
        "tdt.engine.admit", "tdt.batcher.admit", sp.ROUND, "tdt.engine.observe"]
    assert step1.parent is spans.serve
    # 290 - (2 + 150 + 120 + 6)
    assert step1.self_ns == 12 * US
    admit = step1.children[1]
    assert [c.stats["uid"] for c in admit.children] == ["a", "b"]
    assert admit.self_ns == (150 - 80 - 66) * US
    assert admit.children[0].child_ns(".pull") == 66 * US
    # round 2 sampled: 110 - (2 + 4 + 90 + 6)
    round2 = step2.children[1]
    assert round2.self_ns == 8 * US and round2.child_ns(".sample") == 6 * US
    # the serve call is covered by its steps, ingests and sleep but for 30 us
    named = sum(c.dur for c in spans.serve.children)
    assert named == (4 + 290 + 118 + 119 + 3 + 250 + 3 + 184) * US
    assert spans.serve.self_ns == 1000 * US - named


def test_lateness_from_stats(spans):
    # (300 + 0) us over (2 + 1) arrivals
    assert sp.ingest_late_ms(spans) == pytest.approx(0.1)
    assert max(s.stats["late_us_max"] for s in spans.named(sp.INGEST)) == 200


def test_idle_with_work_against_a_hand_made_busy_list(spans):
    busy = tr.busy_intervals(device_trace().line("/device:TPU:0", tr.OPS))
    window_s, with_work_s, asleep_s = sp.idle_split_s(busy, spans)
    assert window_s == pytest.approx(1000e-6)
    # gaps 25 + 9 + 14 + 30 + 24 + 9 + 20 us with the engine awake; the gap
    # 603-915 has its middle under the sleep (650-900) and goes there whole
    assert with_work_s == pytest.approx(131e-6)
    assert asleep_s == pytest.approx(312e-6)
    idle_s = window_s - sum(b - a for a, b in BUSY_US) * 1e-6
    assert with_work_s + asleep_s == pytest.approx(idle_s)


def test_each_of_the_eight_readers_on_the_fixture(spans):
    run = a_run(spans)
    got = {n: cells.load_module("metrics", n).read(run) for n in NEW}
    assert got["engine.ingest_late_ms"] == pytest.approx(0.1)
    # steps that ran the batcher, less its spans: 20, 8, 7, 9 us (the idle
    # poll is left out)
    assert got["engine.step_self_ms"] == pytest.approx(0.011)
    # rounds less their pulls: 20, 20, 42, 15 us
    assert got["batcher.round_host_ms"] == pytest.approx(0.02425)
    assert got["batcher.round_host_max_ms"] == pytest.approx(0.042)
    assert got["batcher.round_pull_ms"] == pytest.approx(0.09)
    # admissions less their pulls: 14, 12 us
    assert got["batcher.prefill_host_ms"] == pytest.approx(0.013)
    assert got["batcher.tokens_per_round"] == pytest.approx(1.5)
    assert got["device.idle_with_work_share"] == pytest.approx(13.1)
    idle = cells.load_module("metrics", "device.idle_share").read(run)
    assert got["device.idle_with_work_share"] < idle
    # the new entries of BENCHMARK.json are these eight, each with a reader
    # whose unit is the entry's
    entries = {m["name"]: m for m in cells.benchmark()["per_layer"]}
    for name in NEW:
        assert cells.load_module("metrics", name).UNIT == entries[name]["unit"]


def test_readers_on_the_recorded_cut():
    """A 1.2 s cut of a real traced run of ``mistral-7b-v0.3.chat`` on a
    v5e: one admission among decode rounds. The expected numbers were read
    off it once; the idle time is the cut less the busy list, all of it
    with work in flight (nobody slept)."""
    cut = cells.load_json(os.path.join(HERE, "fixtures", "spans_recorded.json"))
    want = cells.load_json(os.path.join(HERE, "fixtures",
                                        "spans_recorded.expect.json"))
    spans = sp.Spans.from_json(cut["spans"])
    rounds = spans.named(sp.ROUND)
    assert len(rounds) == want["rounds"]
    assert rounds[0].stats["round"] == want["first_round"]
    assert [c.name.rsplit(".", 1)[1] for c in rounds[0].children] == [
        "upload", "dispatch", "pull"]
    busy = [[a, b] for a, b in cut["busy"]]
    ops = {"names": ["op"], "idx": [0] * len(busy),
           "start": [a for a, _ in busy], "dur": [b - a for a, b in busy]}
    run = bench_run.Run(
        cell=cells.Cell(cells.benchmark(), "mistral-7b-v0.3.chat"),
        trace=tr.Trace.from_json({"/device:TPU:0": {tr.OPS: ops}}),
        plane="/device:TPU:0", window=(0, cut["cut_ns"]), tdt_spans=spans)
    got = {n: cells.load_module("metrics", n).read(run) for n in NEW}
    for name in NEW:
        key = name.split(".", 1)[1]
        assert got[name] == pytest.approx(want[key], rel=1e-6), name
    idle_ns = cut["cut_ns"] - want["busy_ns"]
    assert got["device.idle_with_work_share"] == pytest.approx(
        100.0 * idle_ns / cut["cut_ns"])
    # the pull is the device's step (75.2 ms) and a little more; the host's
    # own share of a round is under 2% of it
    assert 75.0 < got["batcher.round_pull_ms"] < 78.0
    assert got["batcher.round_host_ms"] < 0.02 * got["batcher.round_pull_ms"]


def test_no_tdt_span_reads_as_nothing(tmp_path):
    rows = [{"name": "perfbench.window", "thread": "t", "start": 0, "dur": 9,
             "stats": {}}]
    assert sp.Spans.from_json(rows) is None
    run = a_run(None)
    assert all(cells.load_module("metrics", n).read(run) is None for n in NEW)
    # a run whose trace is not on disk (or was never made) reads the same
    del run.tdt_spans
    run.cell.name = "no-such-cell"
    assert sp.of(run) is None and "tdt_spans" in run.__dict__
    assert all(cells.load_module("metrics", n).read(run) is None for n in NEW)


def test_annotations_of_a_real_profiler_session_are_read(tmp_path):
    """The reader against the profiler itself: annotations written under a
    real session (CPU) come back nested, with their stats as numbers."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("tdt.engine.serve", offered=1):
        with jax.profiler.TraceAnnotation(sp.ROUND, round=7) as ann:
            with jax.profiler.TraceAnnotation(sp.ROUND + ".pull"):
                jax.block_until_ready(jax.numpy.ones(8) + 1)
            ann.set_metadata(tokens=5, finished=1)
        with jax.profiler.TraceAnnotation("perfbench.other"):
            pass
    jax.profiler.stop_trace()
    spans = sp.load_xplane(tr.find_xplane(str(tmp_path)))
    assert [s.name for s in spans.all] == [sp.SERVE, sp.ROUND, sp.ROUND + ".pull"]
    rnd = spans.named(sp.ROUND)[0]
    assert rnd.stats == {"round": 7, "tokens": 5, "finished": 1}
    assert rnd.parent is spans.serve and rnd.children[0].name.endswith(".pull")
    assert 0 < rnd.children[0].dur <= rnd.dur <= spans.serve.dur
    # the plain form round-trips
    again = sp.Spans.from_json(spans.to_json(spans.serve.start, spans.serve.end))
    assert [s.name for s in again.all] == [s.name for s in spans.all]
    assert again.serve.dur == spans.serve.dur
