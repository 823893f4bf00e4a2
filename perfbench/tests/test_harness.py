"""Self-checks of the yardstick: statistics, traffic, trace reduction and
the lint of ``BENCHMARK.json``. No chip, no program."""

import json
import math
import os
import re

import pytest

from harness import cells, stats, trace as tr, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


# -- statistics on hand-made lifecycle records ----------------------------

def rec(uid, due, admitted, first, finished, n, wanted=None):
    return stats.Record(uid, 10, wanted or n, tuple(range(n)), due, admitted,
                        first, finished)


def test_pooled_statistics_by_hand():
    rs = [rec("a", 0.0, 0.1, 0.5, 1.5, 11), rec("b", 1.0, 1.0, 1.2, 3.2, 21)]
    # (1.0 + 2.0) s over (10 + 20) decode tokens
    assert stats.tpot_mean_ms(rs) == pytest.approx(100.0)
    # 32 tokens, window opened at 0, last token at 3.2
    assert stats.tokens_per_s(rs, 0.0) == pytest.approx(10.0)
    assert stats.ttft_ms(rs) == pytest.approx([500.0, 200.0])
    # a's steps read 10 prompt tokens + 1..10 outputs; b's 10 + 1..20
    assert stats.kv_token_reads(rs) == 10 * 10 + 55 + 20 * 10 + 210


def test_a_stall_moves_tpot_and_throughput():
    calm = [rec(f"r{i}", 0.0, 0.0, 0.2, 2.2, 21) for i in range(4)]
    stalled = calm[:3] + [rec("r3", 0.0, 0.0, 0.2, 3.2, 21)]   # 1 s stall
    assert stats.tpot_mean_ms(stalled) > 1.1 * stats.tpot_mean_ms(calm)
    assert stats.tokens_per_s(stalled, 0.0) < 0.75 * stats.tokens_per_s(calm, 0.0)


def test_percentile_is_nearest_rank_and_failures_lie_beyond():
    assert stats.percentile(list(range(1, 11)), 90) == 9
    assert stats.percentile(list(range(1, 11)), 50) == 5
    assert stats.percentile([3.0], 90) == 3.0
    bad = stats.Record("x", 10, 5, (), 0.0, None, None, None)
    rs = [rec(f"r{i}", 0.0, 0.0, 0.1 * (i + 1), 1.0, 5) for i in range(9)] + [bad]
    assert stats.percentile(stats.ttft_ms(rs), 90) == pytest.approx(900.0)
    assert stats.percentile(stats.ttft_ms(rs), 91) == math.inf
    short = rec("s", 0.0, 0.0, 0.1, 1.0, 3, wanted=5)   # fewer tokens than asked
    assert not short.ok and stats.tokens_per_s([short], 0.0) is None


# -- traffic: the same work whatever the seed -----------------------------

def traffic_files():
    d = os.path.join(ROOT, "perfbench", "traffic")
    return sorted(f for f in os.listdir(d) if f.endswith(".json"))


@pytest.mark.parametrize("name", traffic_files())
def test_every_seed_offers_the_same_work(name):
    spec = traffic.load(os.path.join(ROOT, "perfbench", "traffic", name))
    runs = [traffic.generate(spec, 32768, seed, 45.0)
            for seed in (1, 2, 2**31 + 12345)]
    works = [traffic.work(r) for r in runs]
    assert works[0] == works[1] == works[2]
    # ... with other tokens, in the same order and at the same times: the
    # schedule is the mix's own, a fixed trace replayed
    assert runs[0][0].prompt != runs[1][0].prompt
    assert ([(r.uid, len(r.prompt), r.n_out, r.t_s) for r in runs[0]]
            == [(r.uid, len(r.prompt), r.n_out, r.t_s) for r in runs[2]])
    again = traffic.generate(spec, 32768, 2, 45.0)
    assert again == runs[1]
    assert all(0 <= t < 32768 for r in runs[2] for t in r.prompt)
    if spec["process"] == "backlog":
        assert {r.t_s for r in runs[0]} == {0.0}
    else:
        due = [r.t_s for r in runs[0]]
        assert due == sorted(due) and 0 <= due[0] and due[-1] < 45.0
        assert len(due) == round(spec["rate_rps"] * 45.0)


def test_length_distributions():
    q = {"quantiles": [[0, 65], [0.5, 200], [1, 1024]]}
    assert traffic.ppf(q, 0) == 65 and traffic.ppf(q, 0.5) == 200
    assert traffic.ppf(q, 1) == 1024 and traffic.max_len(q) == 1024
    lens = traffic.stratified(q, 100)
    assert lens == sorted(lens) and 65 <= lens[0] and lens[-1] <= 1024
    assert abs(sorted(lens)[50] - 200) <= 5
    u = {"uniform": [129, 512]}
    assert traffic.ppf(u, 0) == 129 and traffic.ppf(u, 1) == 512
    with pytest.raises(ValueError):
        traffic.ppf({"fixed": 7}, 0.5)


def test_a_mix_that_needs_code_the_generator_lacks_is_refused(tmp_path):
    """Sessions, shared prefixes and bursts come with the cells that need
    them; until then a file that asks for them fails, loudly."""
    good = {"process": "poisson", "rate_rps": 2.0, "_note": "a comment",
            "prompt_len": {"uniform": [4, 8]}, "output_len": {"uniform": [2, 4]}}
    path = tmp_path / "mix.json"
    for extra in ({}, {"turns": 2}, {"prefix_pool": 3}, {"process": "burst"},
                  {"schedule_seed": 1}):
        path.write_text(json.dumps(dict(good, **extra)))
        if extra:
            with pytest.raises(ValueError):
                traffic.load(str(path))
        else:
            assert len(traffic.generate(traffic.load(str(path)), 100, 9, 10.0)) == 20


# -- trace reduction on the small recorded trace --------------------------

RECORDED = os.path.join(HERE, "fixtures", "trace_small.json")


def test_union_and_gaps_by_hand():
    ev = tr.Events(["a", "b"], [0, 1, 0, 1], [0, 5, 20, 22], [10, 2, 5, 10])
    assert tr.busy_intervals(ev).tolist() == [[0, 10], [20, 32]]
    assert tr.busy_s(ev) == pytest.approx(22e-9)
    assert ev.by_name() == [("a", 2, 15e-9), ("b", 2, 12e-9)]
    spans = tr.Events(["perfbench.window", "perfbench.x"], [0, 1], [0, 10], [40, 10])
    gaps = tr.idle_gaps(tr.busy_intervals(ev), (0, 40), spans)
    assert gaps == [["perfbench.x", 10e-9], ["perfbench.window", 8e-9]]
    outer = tr.Events(["m"], [0, 0], [0, 20], [10, 10])
    assert len(ev.inside(outer)) == 4 and len(ev.within(0, 20)) == 2
    assert ev.matching("^a$").total_s() == pytest.approx(15e-9)


def test_reduction_of_the_recorded_trace():
    """A cut of a real traced run of ``mistral-7b-v0.3.chat`` on a v5e
    (``tools/tracedump.py --cut``); the expected numbers were read off it
    once, by hand."""
    want = cells.load_json(os.path.join(HERE, "fixtures", "trace_small.expect.json"))
    t = tr.load_json(RECORDED)
    plane = t.device_planes()[0]
    assert plane == want["plane"]
    ops, mods = t.line(plane, tr.OPS), t.line(plane, tr.MODULES)
    span = (0, int(want["cut_ns"]))
    steps = mods.matching(want["decode_step"])
    assert len(steps) == want["n_steps"]
    assert steps.total_s() == pytest.approx(want["steps_s"], rel=1e-9)
    assert tr.busy_s(ops) == pytest.approx(want["busy_s"], rel=1e-9)
    assert tr.busy_s(ops) <= sum(ops.dur) / 1e9 + 1e-12
    idle = 1 - tr.busy_s(ops.within(*span)) / (span[1] / 1e9)
    assert idle == pytest.approx(want["idle_share"], rel=1e-6)
    top = ops.by_name()[0]
    assert [top[0], top[1]] == want["top_op"][:2]
    assert top[2] == pytest.approx(want["top_op"][2], rel=1e-9)
    inside = ops.inside(steps)
    assert 0 < len(inside) <= len(ops)
    assert inside.total_s() <= steps.total_s() * 1.001
    gaps = tr.idle_gaps(tr.busy_intervals(ops), span, t.host_spans())
    assert gaps and gaps[0][0].startswith("perfbench.")
    assert sum(g[1] for g in gaps) <= span[1] / 1e9


# -- lint of BENCHMARK.json ------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTHS = re.compile(r"(hidden|intermediate|ffn|latent|state|proj|_dim$|_rank$|"
                    r"head_dim|head_size|expansion|experts_per_tok|topk)")


def test_benchmark_json_lint():
    path = os.path.join(ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    b = cells.load_json(path)
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(b["paths"]) <= 16 and 1 <= len(b["command"]) <= 32
    under = lambda f: any(f.startswith(p + "/") for p in b["paths"])  # noqa: E731
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (b["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200

    confs = {}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] not in confs
        assert under(c["file"]) and os.path.isfile(os.path.join(ROOT, c["file"]))
        assert len(c["reduced"]) <= 16
        body = cells.load_json(os.path.join(ROOT, c["file"]))
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTHS.search(key), key
            assert key in body, f"{c['name']}: reduced key {key} is not in the file"
        assert sorted(c["reduced"]) == sorted(body.get("reduced", []))
        for k in ("program", "reference"):
            cells.load_module(k + "s", body[k])
        assert set(body["limits"]) >= {"max_gap", "mean_gap"}
        confs[c["name"]] = c
    assert len({c["file"] for c in b["configs"]}) == len(confs)

    cellnames = []
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in confs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        cell = cells.Cell(b, w["name"])
        traffic.load(cell.traffic_path)
        assert cell.config["chips"] == w["chips"]
        cellnames.append(w["name"])
    assert len(set(cellnames)) == len(cellnames)
    assert len({(w["config"], w["traffic"]) for w in b["workloads"]}) == len(cellnames)
    assert {w["config"] for w in b["workloads"]} == set(confs)
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(cellnames) // 4)

    e2e = {}
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert set(m.get("workloads", cellnames)) <= set(cellnames)
        if m["name"] != "setup_s":
            assert cells.load_module("metrics", m["name"]).UNIT == m["unit"]
        e2e[m["name"]] = set(m.get("workloads", cellnames))
    assert e2e["setup_s"] == set(cellnames)

    layers = set()
    names = set(e2e)
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["name"] not in names
        names.add(m["name"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        listed = set(m.get("workloads", e2e[m["moves"]]))
        assert listed <= e2e[m["moves"]], (
            f"{m['name']} lists a cell that does not report {m['moves']}")
        assert cells.load_module("metrics", m["name"]).UNIT == m["unit"]
        layers.add(m["layer"])
    for cell in cellnames:
        assert any(cell in s and n != "setup_s" for n, s in e2e.items())
        assert any(cell in m.get("workloads", e2e[m["moves"]])
                   for m in b["per_layer"])
    assert len(layers) <= 8


def test_peaks_table_names_its_source_and_guesses_nothing():
    table = cells.load_json(os.path.join(ROOT, "perfbench", "harness", "peaks.json"))
    assert "TPU v5e" in table["_source"]
    p = cells.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["int8_ops_per_s"] == 393e12
    with pytest.raises(SystemExit):
        cells.peaks("TPU v99")


# -- the per-layer readers on the recorded trace ---------------------------

def test_readers_on_the_recorded_trace():
    """Every reader named in ``BENCHMARK.json`` over the recorded cut, with
    lifecycle records made by hand: what the trace holds is read, a share
    of a roofline stays under 100%, and a reader that finds nothing to
    read (no collective on one chip) returns nothing."""
    import run as bench_run

    b = cells.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = cells.Cell(b, "mistral-7b-v0.3.chat")
    t = tr.load_json(RECORDED)
    want = cells.load_json(os.path.join(HERE, "fixtures", "trace_small.expect.json"))
    span = (0, int(want["cut_ns"]))
    n_steps = len(t.line(want["plane"], tr.MODULES).matching(
        want["decode_step"]).within(*span))
    # eight requests that decode through the whole cut, one token a step
    records = [stats.Record(f"w{i}", 300, n_steps + 1, tuple(range(n_steps + 1)),
                            0.0, 0.01, 0.05, 1.2) for i in range(8)]
    adapter = cells.load_module("programs", cell.config["program"])
    run = bench_run.Run(
        cell=cell, config=cell.config, sizes=cell.config["sizes"],
        records=records, t_open=0.0, seconds=1.2, setup_s=1.0, chips=1,
        weight_bytes=7.25e9 * 2 * 16 / 32 + 32768 * 4096 * 2,
        prefill_rows={r.uid: 8 * 256 for r in records},   # the cut's bucket
        peaks=cells.peaks("TPU v5 lite"), programs=adapter.PROGRAMS,
        trace=t, plane=want["plane"], window=span)
    got = {m["name"]: cells.load_module("metrics", m["name"]).read(run)
           for m in b["per_layer"]}
    assert got["batcher.tokens_per_step"] == pytest.approx(8.0)
    assert got["step.decode_device_ms"] == pytest.approx(
        want["steps_s"] / want["n_steps"] * 1e3, rel=0.02)
    assert got["device.idle_share"] == pytest.approx(100 * want["idle_share"], rel=1e-6)
    # the world-1 prefill GEMMs carry the ring kernels' names too
    for name in ("step.decode_roofline", "kernel.flash_decode_roofline",
                 "kernel.ag_gemm_roofline"):
        assert 1.0 < got[name] < 100.0, (name, got[name])
    assert got["engine.queue_wait_ms"] == pytest.approx(10.0)
    assert got["engine.ttft_p50_ms"] == pytest.approx(50.0)
    assert got["engine.ttft_p90_ms"] == pytest.approx(50.0)
    run.records.append(stats.Record("bad", 10, 5, (), 0.0, None, None, None))
    mean = cells.load_module("metrics", "ttft_mean_ms").read
    assert mean(run) == math.inf        # left out of the line by run.py
    run.records.pop()
    assert mean(run) == pytest.approx(50.0)
    assert got["batcher.admit_to_first_ms"] == pytest.approx(40.0)
    assert got["collective.exposed_share"] is None
    assert got["step.prefill_device_ms"] == pytest.approx(
        want["prefills_s"] / want["n_prefills"] * 1e3, rel=1e-6)
    run.programs = dict(run.programs, prefill="^jit_no_such_program")
    assert cells.load_module("metrics", "step.prefill_device_ms").read(run) is None
    ops = bench_run.breakdown(run)
    assert 0 < len(ops["device_ops"]) <= 10 and len(ops["idle_gaps"]) <= 10
    assert ops["device_ops"][0][1] >= ops["device_ops"][-1][1] > 0
