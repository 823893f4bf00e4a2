"""The power-retention configuration's files: the cell's files through
``cells.Cell``, the catalog row's keys and the parameter count, the cell's
traffic, the nine readers this configuration brings on a small hand-made
trace (``fixtures/retention_small.json``), how far back the reference's
sum remembers, a whole toy run of the adapter and reference through
``run.py`` (CPU, interpreted kernels, ``tests/tiny_retention``: two rounds
of the slots, prompts of one chunk and of two, lookahead on) and a planted
fault shown not correct through the tool that plants them on the chip. The
real configuration's limits are set from chip readings (``PERF.md``)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run as bench_run
from harness import cells, scopes as sc, spans as sp, stats, trace as tr, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = cells.load_json(os.path.join(HERE, "tiny_retention", "BENCHMARK.json"))
CELL = "brumby-14b-base.doc-reason-b16"
NEW_METRICS = {
    "step.retention_decode_roofline", "kernel.retention_update_roofline",
    "kernel.retention_prefill_roofline", "retention.state_bytes_share",
    "prefill.retention_admit_device_ms", "step.retn_ms", "step.retn_ffn_ms",
    "step.retn_head_ms", "step.retn_unscoped_share"}


def cpu_devices(cell):
    return jax.devices()[: cell.chips]


def test_the_configuration_file_holds_the_catalog_rows_keys_but_the_depth():
    bench = cells.benchmark()
    cell = cells.Cell(bench, CELL)
    c = cell.config
    row = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(row):
        with open(row) as f:
            rows = [json.loads(line) for line in f]
        (pub,) = [r for r in rows if r["name"] == "Brumby-14B-Base"]
        assert c["source"] == pub["source_url"]
        for key, value in pub["config"].items():
            assert c[key] == value, key
    entry = {e["name"]: e for e in bench["configs"]}[c["name"]]
    assert entry["reduced"] == c["reduced"] == ["n_layers"]
    assert (c["hidden"], c["ffn"], c["n_q_heads"],
            c["n_kv_heads"], c["head_dim"], c["vocab"]) == (
        c["hidden_size"], c["intermediate_size"],
        c["num_attention_heads"], c["num_key_value_heads"], 128,
        c["vocab_size"]) == (5120, 17408, 40, 8, 128, 151936)
    assert (c["n_layers"], c["num_hidden_layers"]) == (6, 40)
    assert (c["rope_theta"], c["norm_eps"], c["power"]) == (
        1_000_000, c["rms_norm_eps"], 2)
    assert "lookahead" not in c["engine"]       # the batcher's own default
    assert c["engine"] == {"slots": 16, "s_max": 16384, "page": 128,
                           "max_queue": 4096}
    for key in ("power", "gate", "normaliser", "head_norms_and_rotation",
                "state_dtype", "state_rows", "weights"):
        assert key in c["assumed"], key
    reference = cells.load_module("references", c["reference"])
    reference.configure(c)
    assert reference.count_parameters(dict(c["sizes"], n_layers=40)) \
        == 14_769_945_920
    assert "14,769,945,920" in c["parameters"]
    adapter = cells.load_module("programs", c["program"])
    cfg = adapter.model_config(c)
    assert (cfg.cache_kind, cfg.batch, cfg.state_rows) == ("state", 16, 8704)
    assert cfg.state_bytes() == 6 * 2 * 16 * 36_175_872
    # the counts under the metrics are of the state the program holds
    run = bench_run.Run(sizes=c["sizes"], config=c)
    step = run.kernel("retention_decode_step")
    assert step.state_bytes_per_slot(run) * 16 == cfg.state_bytes()
    from triton_dist_tpu.ops import retention as rt
    assert run.kernel("retention_prefill").CHUNK == rt.chunk_len(128)


def test_the_cells_traffic_is_one_round_of_the_slots():
    bench = cells.benchmark()
    cell = cells.Cell(bench, CELL)
    c = cell.config
    spec = traffic.load(cell.traffic_path)
    assert {k: v for k, v in spec.items() if not k.startswith("_")} == {
        "process": "backlog", "backlog_tokens_per_s": 273.1,
        "prompt_len": {"uniform": [4097, 8192]},
        "output_len": {"uniform": [512, 1024]},
        "temperature": 0.0, "check_requests": 3}
    reqs = traffic.generate(spec, c["vocab"], 2**31 + 5, bench["run_seconds"])
    work = traffic.work(reqs)
    assert work["requests"] == 16 == c["engine"]["slots"]
    assert work["output_tokens"] == 12288
    assert all(r.t_s == 0.0 for r in reqs)
    assert max(len(r.prompt) + r.n_out for r in reqs) <= c["engine"]["s_max"]
    assert {8192} == {1 << (len(r.prompt) - 1).bit_length() for r in reqs}
    assert cell.chips == 1 and set(cell.end_to_end) == {
        "tpot_mean_ms", "tokens_per_s", "setup_s"}
    assert set(cell.per_layer) == NEW_METRICS | {
        "batcher.tokens_per_step", "step.decode_device_ms", "device.idle_share"}


def _fixture_run():
    cell = cells.Cell(cells.benchmark(), CELL)
    fx = cells.load_json(os.path.join(HERE, "fixtures", "retention_small.json"))
    t = tr.Trace.from_json(fx["trace"])
    adapter = cells.load_module("programs", cell.config["program"])
    records = [stats.Record("w0", 6000, 600, tuple(range(600)), 0.0, 0.0, 0.41, 9.0)]
    run = bench_run.Run(
        cell=cell, config=cell.config, sizes=cell.config["sizes"],
        records=records, t_open=0.0, seconds=1.0, setup_s=1.0, chips=1,
        weight_bytes=5.52e9, prefill_rows={"w0": 8192},
        peaks=cells.peaks("TPU v5 lite"), programs=adapter.PROGRAMS,
        trace=t, plane="/device:TPU:0", window=t.window())
    run.tdt_spans = sp.Spans.from_json(fx["spans"])
    run.tdt_scopes = sc.Scopes.from_json(
        fx["scopes"], t.line(run.plane, tr.OPS).names)
    return run, fx["expect"]


def test_the_nine_readers_on_the_hand_made_trace():
    """Each against the arithmetic written out in the fixture's ``_how``;
    the list-less readers find the same programs; no share passes 100%."""
    run, want = _fixture_run()
    for name in sorted(NEW_METRICS | {"step.decode_device_ms"}):
        mod = cells.load_module("metrics", name)
        got = mod.read(run)
        assert got == pytest.approx(want[name], rel=1e-9), name
        assert mod.UNIT != "%" or got < 100.0
    kern = run.kernel("retention_decode_step")
    assert (kern.state_rows(128), kern.state_bytes_per_slot(run),
            kern.slots_per_round(run)) == (8704, 434_110_464, 16.0)


def test_the_readers_read_nothing_where_the_program_lacks_what_they_read():
    """A parent commit's run (no ``state_slots`` on the round, no
    ``prompt_chunks`` on the admission, no such kernel in the trace, no
    ``retn`` scope): nothing, and no error."""
    run, _ = _fixture_run()
    for s in run.tdt_spans.all:
        s.stats.pop("state_slots", None)
        s.stats.pop("prompt_chunks", None)
    ops = run.trace.planes["/device:TPU:0"][tr.OPS]
    ops.names = [n.replace("retention_", "other_") for n in ops.names]
    run.tdt_scopes = sc.Scopes(
        [None if s and s[0] == "retn" else s for s in run.tdt_scopes.scopes])
    for name in NEW_METRICS - {
            "prefill.retention_admit_device_ms", "step.retn_ffn_ms",
            "step.retn_head_ms", "step.retn_unscoped_share"}:
        assert cells.load_module("metrics", name).read(run) is None, name
    run.tdt_scopes = None       # a program with no scope at all
    run.__dict__.pop("tdt_inside", None)
    for name in ("step.retn_ms", "step.retn_ffn_ms", "step.retn_head_ms",
                 "step.retn_unscoped_share"):
        assert cells.load_module("metrics", name).read(run) is None, name


def test_how_far_back_the_sum_remembers():
    """The reading the reference's docstring states, at toy size: with the
    gate's initialisation (``-log g`` in [1e-4, 1e-2]) a token changed 48
    positions back still moves the last position's logits; with a plain
    normal gate bias (``g ~ 0.5``) it does not. Without this a stale state
    or a doubled step would move no logit a few tokens on."""
    cell = cells.Cell(BENCH, "tiny-retention.batch")
    sizes = cell.config["sizes"]
    ref = cells.load_module("references", "brumby_retention")
    ref.configure(cell.config)
    key = ref.seed_key(3)
    plain = [ref.layer_weights(key, li, sizes) for li in range(2)]
    outer = ref.outer_weights(key, sizes)
    forgetful = [dict(w, b_g=jnp.zeros_like(w["b_g"])) for w in plain]

    @jax.jit
    def last_logits(layers, tokens):
        x = outer["embed"][tokens][None].astype(jnp.float32)
        for w in layers:
            x = ref.layer(x, w, sizes)
        return ref.head(x, outer, jnp.array([tokens.shape[0] - 1]), 1,
                        sizes, False)[0, 0]

    rng = np.random.default_rng(0)
    moved = {}
    for name, layers in (("published", plain), ("forgetful", forgetful)):
        for back in (1, 48):
            deltas = []
            for _ in range(4):
                tokens = rng.integers(0, sizes["vocab"], 64)
                other = tokens.copy()
                other[-1 - back] = (other[-1 - back] + 7) % sizes["vocab"]
                deltas.append(float(jnp.abs(
                    last_logits(layers, jnp.asarray(tokens))
                    - last_logits(layers, jnp.asarray(other))).max()))
            moved[name, back] = float(np.mean(deltas))
    assert moved["published", 48] > 0.05 * moved["published", 1]
    assert moved["forgetful", 48] < 1e-4 * moved["forgetful", 1]     # rounding


def _toy_run(capsys, seed, fault=None):
    argv = ["--workload", "tiny-retention.batch", "--seed", str(seed),
            "--seconds", "2", "--trace", "0"]
    if fault is None:
        rc = bench_run.main(argv, devices=cpu_devices, bench=BENCH)
    else:
        faults = cells.load_module("tools", "retention_faults")
        with faults.planted(fault):
            rc = bench_run.main(argv, devices=cpu_devices, bench=BENCH)
    out, _ = capsys.readouterr()
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1])


def test_a_toy_run_through_the_adapter_and_the_reference(capsys):
    result = _toy_run(capsys, 2**31 + 11)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 6 and result["compiles_in_window"] == 0
    assert set(result["metrics"]) == {"tpot_mean_ms", "tokens_per_s", "setup_s"}
    assert result["numbers"]["health_flips"] == [0, 0]


@pytest.mark.parametrize("fault", ["stale_state_kept", "step_twice"])
def test_a_planted_state_fault_is_not_correct(capsys, fault):
    """Through ``run.py`` whole, planted as the chip runs of PERF.md
    section 4 plant it (``tools/retention_faults.py``)."""
    result = _toy_run(capsys, 2**31 + 12, fault)
    assert result["failed"] == 0 and result["correct"] is False
    over = {name for name, (value, limit) in result["numbers"].items()
            if value > limit}
    assert over & {"max_gap", "mean_gap"}
