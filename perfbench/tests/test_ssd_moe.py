"""The Mamba-2 / expert configuration's files: the cell's files through
``cells.Cell``, the catalog row's keys and the parameter count, the cell's
traffic, the twelve readers this configuration brings on a small hand-made
trace (``fixtures/ssd_small.json``), a whole toy run of the adapter and
reference through ``run.py`` (CPU, interpreted kernels, ``tests/tiny_ssd``:
two rounds of the slots, prompts of three and four chunks, lookahead on)
and the three planted faults shown not correct through the tool that plants
them on the chip. The real configuration's limits are set from chip
readings (``PERF.md``)."""

import json
import os

import jax
import pytest

import run as bench_run
from harness import cells, scopes as sc, spans as sp, stats, trace as tr, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = cells.load_json(os.path.join(HERE, "tiny_ssd", "BENCHMARK.json"))
CELL = "granite-4.0-h-small-ep4.doc-reason"
NEW_METRICS = {
    "step.ssd_moe_decode_roofline", "kernel.ssd_state_update_roofline",
    "kernel.ssd_chunk_scan_roofline", "kernel.ssd_expert_gemm_roofline",
    "ssd.state_bytes_share", "moe.ssd_held_experts_hit_per_layer",
    "prefill.ssd_admit_device_ms", "step.ssd_mixer_ms", "step.ssd_attn_ms",
    "step.ssd_ffn_ms", "step.ssd_head_ms", "step.ssd_unscoped_share"}
SCOPE_METRICS = {"step.ssd_mixer_ms", "step.ssd_attn_ms", "step.ssd_ffn_ms",
                 "step.ssd_head_ms", "step.ssd_unscoped_share"}


def cpu_devices(cell):
    return jax.devices()[: cell.chips]


def test_the_configuration_file_holds_the_catalog_rows_keys_but_the_share():
    bench = cells.benchmark()
    cell = cells.Cell(bench, CELL)
    c = cell.config
    row = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(row):
        with open(row) as f:
            rows = [json.loads(line) for line in f]
        (pub,) = [r for r in rows if r["name"] == "granite-4.0-h-small"]
        assert c["source"] == pub["source_url"]
        for key, value in pub["config"].items():
            if key != "num_local_experts":
                assert c[key] == value, key
        assert pub["config"]["num_local_experts"] \
            == c["published"]["num_local_experts"]
    entry = {e["name"]: e for e in bench["configs"]}[c["name"]]
    assert entry["reduced"] == c["reduced"] == [
        "n_layers", "num_local_experts", "vocab"]
    assert (c["n_layers"], c["num_hidden_layers"]) == (10, 40)
    assert (c["num_local_experts"], c["published"]["num_local_experts"]) \
        == (18, 72)
    assert (c["vocab"], c["vocab_size"]) == (25088, 100352)
    assert (c["experts_held"], c["vocab_held"]) == ([0, 18], [0, 25088])
    assert len(c["layer_types"]) == 40
    # no width is cut
    assert (c["hidden"], c["n_q_heads"], c["n_kv_heads"], c["head_dim"]) == (
        c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"],
        128) == (4096, 32, 8, 128)
    assert (c["mamba_n_heads"] * c["mamba_d_head"], c["mamba_d_state"],
            c["mamba_d_conv"], c["mamba_chunk_size"], c["intermediate_size"],
            c["shared_intermediate_size"], c["num_experts_per_tok"]) == (
        8192, 128, 4, 256, 768, 1536, 10)
    assert (c["norm_eps"], c["ffn"]) == (c["rms_norm_eps"], 768)
    assert "lookahead" not in c["engine"]       # the batcher's own default
    assert c["engine"] == {"slots": 32, "s_max": 16384, "page": 128,
                           "max_queue": 4096}
    for key in ("head_dim", "expert_width", "time_step", "state_dtype",
                "gated_norm", "router", "weights"):
        assert key in c["assumed"], key
    adapter = cells.load_module("programs", c["program"])
    cfg = adapter.model_config(c)
    assert (cfg.cache_kind, cfg.batch, cfg.conv_channels) == (
        "kv_state", 32, 8448)
    assert cfg.state_bytes() == 32 * 76_713_984
    assert "2,955,758,208" in c["held"]["parameters"]
    assert "32,207,337,984" in c["published"]["parameters"]
    # the counts under the metrics are of the state the program holds
    run = bench_run.Run(sizes=c["sizes"], config=c)
    step = run.kernel("ssd_moe_decode_step")
    assert step.state_bytes_per_slot(run) * 32 == cfg.state_bytes()
    assert step.mamba_layers(run) == 9
    assert run.kernel("ssd_expert_gemm").bank_bytes(run) \
        == 10 * 18 * 3 * 4096 * 768 * 2


def test_the_cells_traffic_is_one_round_of_the_slots():
    bench = cells.benchmark()
    cell = cells.Cell(bench, CELL)
    c = cell.config
    spec = traffic.load(cell.traffic_path)
    assert cell.traffic_path.endswith("traffic/doc-reason.json")
    reqs = traffic.generate(spec, c["vocab"], 2**31 + 5, bench["run_seconds"])
    work = traffic.work(reqs)
    assert work["requests"] == 32 == c["engine"]["slots"]
    assert all(r.t_s == 0.0 for r in reqs)
    assert max(len(r.prompt) + r.n_out for r in reqs) <= c["engine"]["s_max"]
    assert {8192} == {1 << (len(r.prompt) - 1).bit_length() for r in reqs}
    assert max(max(r.prompt) for r in reqs) < c["vocab"]
    assert cell.chips == 1 and set(cell.end_to_end) == {
        "tpot_mean_ms", "tokens_per_s", "setup_s"}
    # `<=`: a later PR's list-less metric joins every cell's set
    assert NEW_METRICS | {
        "batcher.tokens_per_step", "step.decode_device_ms",
        "device.idle_share"} <= set(cell.per_layer)


def _fixture_run():
    cell = cells.Cell(cells.benchmark(), CELL)
    fx = cells.load_json(os.path.join(HERE, "fixtures", "ssd_small.json"))
    t = tr.Trace.from_json(fx["trace"])
    adapter = cells.load_module("programs", cell.config["program"])
    records = [stats.Record("w0", 6000, 600, tuple(range(600)), 0.0, 0.0, 0.41, 9.0)]
    run = bench_run.Run(
        cell=cell, config=cell.config, sizes=cell.config["sizes"],
        records=records, t_open=0.0, seconds=1.0, setup_s=1.0, chips=1,
        weight_bytes=5_911_516_416.0, prefill_rows={"w0": 8192},
        peaks=cells.peaks("TPU v5 lite"), programs=adapter.PROGRAMS,
        trace=t, plane="/device:TPU:0", window=t.window())
    run.tdt_spans = sp.Spans.from_json(fx["spans"])
    run.tdt_scopes = sc.Scopes.from_json(
        fx["scopes"], t.line(run.plane, tr.OPS).names)
    return run, fx["expect"]


def test_the_twelve_readers_on_the_hand_made_trace():
    """Each against the arithmetic written out in the fixture's ``_how``;
    the list-less readers find the same programs; no share passes 100%."""
    run, want = _fixture_run()
    for name in sorted(NEW_METRICS | {"step.decode_device_ms"}):
        mod = cells.load_module("metrics", name)
        got = mod.read(run)
        assert got == pytest.approx(want[name], rel=1e-9), name
        assert mod.UNIT != "%" or got < 100.0
    parts = run.kernel("ssd_moe_decode_step").parts(run)
    assert {k: int(v) for k, v in parts.items()} == dict(
        weights=2_514_130_176, experts=3_303_014_400, state=2_454_847_488,
        pages=819_200_000)


def test_the_readers_read_nothing_where_the_program_lacks_what_they_read():
    """A parent commit's run (no such counters on the round or the
    admission, no such kernel in the trace, no scope at all): nothing, and
    no error."""
    run, _ = _fixture_run()
    for s in run.tdt_spans.all:
        for k in ("state_slots", "prompt_chunks", "experts_hit"):
            s.stats.pop(k, None)
    ops = run.trace.planes["/device:TPU:0"][tr.OPS]
    ops.names = [n.replace("ssd_", "other_") for n in ops.names]
    for name in NEW_METRICS - SCOPE_METRICS - {"prefill.ssd_admit_device_ms"}:
        assert cells.load_module("metrics", name).read(run) is None, name
    run.tdt_scopes = None       # a program with no scope at all
    run.__dict__.pop("tdt_inside", None)
    for name in SCOPE_METRICS:
        assert cells.load_module("metrics", name).read(run) is None, name


def _toy_run(capsys, seed, fault=None):
    argv = ["--workload", "tiny-ssd.batch", "--seed", str(seed),
            "--seconds", "2", "--trace", "0"]
    if fault is None:
        rc = bench_run.main(argv, devices=cpu_devices, bench=BENCH)
    else:
        faults = cells.load_module("tools", "ssd_faults")
        with faults.planted(fault):
            rc = bench_run.main(argv, devices=cpu_devices, bench=BENCH,
                                tamper=faults.tamper_of(fault))
    out, _ = capsys.readouterr()
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1])


def test_a_toy_run_through_the_adapter_and_the_reference(capsys):
    result = _toy_run(capsys, 2**31 + 11)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 6 and result["compiles_in_window"] == 0
    assert set(result["metrics"]) == {"tpot_mean_ms", "tokens_per_s", "setup_s"}
    assert result["numbers"]["health_flips"] == [0, 0]


@pytest.mark.parametrize(
    "fault", ["stale_state_kept", "advanced_twice", "carry_dropped"])
def test_a_planted_state_fault_is_not_correct(capsys, fault):
    """Through ``run.py`` whole, planted as the chip runs of PERF.md
    section 4 plant it (``tools/ssd_faults.py``)."""
    result = _toy_run(capsys, 2**31 + 11, fault)
    assert result["failed"] == 0 and result["correct"] is False
    over = {name for name, (value, limit) in result["numbers"].items()
            if value > limit}
    assert over & {"max_gap", "mean_gap"}
