"""The SmallThinker configuration's files: the parameter arithmetic its
file states and the cell's traffic, the reference's blocked run against
its whole one, the counting functions of the new metrics, a whole toy run
of its adapter and reference through ``run.py`` (CPU, interpreted kernels,
``tests/tiny_prerouted``: rings that wrap, lookahead on) and a planted
ring fault shown not correct. The real configuration's limits are set
from chip readings (``PERF.md``)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

import run as bench_run
from harness import cells, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = cells.load_json(os.path.join(HERE, "tiny_prerouted", "BENCHMARK.json"))
CELL = "smallthinker-21b-a3b.doc-reason"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def cpu_devices(cell):
    return jax.devices()[: cell.chips]


def parameters(c: dict, layers: int) -> int:
    """Parameters of ``layers`` layers with the embedding, the head and the
    final norm, from the published keys."""
    h, d = c["hidden_size"], c["head_dim"]
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    attn = 2 * h * hq * d + 2 * h * hkv * d                # q, o, k, v
    expert = 3 * h * c["moe_ffn_hidden_size"]
    router = h * c["moe_num_primary_experts"]
    layer = attn + router + c["moe_num_primary_experts"] * expert + 2 * h
    return layers * layer + 2 * c["vocab_size"] * h + h


def test_the_configuration_files_parameter_arithmetic():
    bench = cells.benchmark()
    cell = cells.Cell(bench, CELL)
    c = cell.config
    whole = parameters(c, c["published"]["num_hidden_layers"])
    assert whole == 21_506_562_560 and c["num_hidden_layers"] == 52
    held = parameters(c, c["n_layers"])
    assert held == 3_966_937_600
    assert "21,506,562,560" in c["published"]["parameters"]
    assert "3,966,937,600" in c["held"]["parameters"]
    entry = {e["name"]: e for e in bench["configs"]}[c["name"]]
    assert entry["reduced"] == c["reduced"] == ["n_layers"]
    assert entry["source"] == c["source"]
    # no width is cut, nothing but the depth is
    assert (c["hidden"], c["n_q_heads"], c["n_kv_heads"], c["head_dim"],
            c["vocab"], c["n_layers"]) == (2560, 28, 4, 128, 151936, 8)
    assert (c["hidden"], c["n_q_heads"], c["n_kv_heads"], c["vocab"]) == (
        c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"],
        c["vocab_size"])
    assert (c["moe_num_primary_experts"], c["moe_num_active_primary_experts"],
            c["moe_ffn_hidden_size"], c["sliding_window_size"]) == (
        64, 6, 768, 4096)
    assert (c["rope_theta"], c["norm_eps"]) == (1500000, c["rms_norm_eps"])
    # no layer reads the harness's `ffn`: it holds the expert width
    assert c["ffn"] == c["moe_ffn_hidden_size"] and "_ffn" in c
    # every key of the catalog's row under its own name, where the catalog
    # is on this machine
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "SmallThinker-21BA3B-Instruct")
        assert row["source_url"] == c["source"]
        for key, value in row["config"].items():
            assert c[key] == value, key
    # what the program is built from: two whole periods, the router at its
    # published width, rings of 33 pages
    adapter = cells.load_module("programs", c["program"])
    cfg = adapter.model_config(c)
    assert cfg.layer_types == ("full", "window", "window", "window") * 2
    assert (cfg.window, cfg.n_experts, cfg.topk, cfg.expert_ffn, cfg.held,
            cfg.first_k_dense, cfg.n_shared_experts) == (
        4096, 64, 6, 768, (0, 64), 0, 0)
    assert (cfg.norm_placement, cfg.qk_norm, cfg.router_rows, cfg.scoring,
            cfg.gate_act, cfg.cache_kind) == (
        "input", False, "layer_input", "softmax", "relu", "kv_window")
    from triton_dist_tpu.models.decode import WindowPagedKVCacheSpec

    eng = c["engine"]
    spec = WindowPagedKVCacheSpec(eng["s_max"], eng["page"], static_table=True)
    assert (spec.ring(cfg), eng["s_max"] // eng["page"]) == (33, 128)


def test_the_cells_traffic_is_one_round_of_the_slots_past_the_window():
    bench = cells.benchmark()
    cell = cells.Cell(bench, CELL)
    c = cell.config
    spec = traffic.load(cell.traffic_path)
    assert {k: v for k, v in spec.items() if not k.startswith("_")} == {
        "process": "backlog", "backlog_tokens_per_s": 546.2,
        "prompt_len": {"uniform": [4097, 8192]},
        "output_len": {"uniform": [512, 1024]},
        "temperature": 0.0, "check_requests": 3}
    reqs = traffic.generate(spec, c["vocab"], 2**31 + 5, bench["run_seconds"])
    work = traffic.work(reqs)
    assert work["requests"] == 32 == c["engine"]["slots"]
    assert (work["prompt_tokens"], work["output_tokens"]) == (196640, 24576)
    assert all(r.t_s == 0.0 for r in reqs)
    # every prompt is past the window, in one bucket; every context fits
    assert min(work["prompt_lens"]) > c["sliding_window_size"]
    assert max(work["prompt_lens"]) <= 8192
    assert max(len(r.prompt) + r.n_out for r in reqs) <= c["engine"]["s_max"]
    assert max(max(r.prompt) for r in reqs) < c["vocab"]
    assert cell.chips == 1 and set(cell.end_to_end) == {
        "tpot_mean_ms", "tokens_per_s", "setup_s"}
    # a subset: a later benchmark PR may list more for this cell
    assert {"kernel.flash_prefill_roofline", "prefill.admit_device_ms",
            "kernel.ring_decode_roofline", "step.ring_moe_decode_roofline",
            "attn.rows_read_share", "moe.bank_experts_hit_per_layer",
            "step.route_ms", "batcher.tokens_per_step",
            "step.decode_device_ms", "device.idle_share"} <= set(cell.per_layer)
    for name in cell.per_layer:
        assert hasattr(cells.load_module("metrics", name), "read"), name


def test_the_reference_in_blocks_is_the_reference_whole():
    """Queries 8 at a time and the bank 3 experts at a time (``logits``,
    as the cell runs it) against one layer at a time whole (``layer``)."""
    cell = cells.Cell(BENCH, "tiny-prerouted.batch", root=os.path.dirname(
        os.path.dirname(HERE)))
    ref = cells.load_module("references", cell.config["reference"])
    ref.configure(cell.config)
    sizes = cell.config["sizes"]
    tokens = np.random.default_rng(0).integers(0, sizes["vocab"], (2, 24))
    first = np.array([3, 10], np.int32)
    got = ref.logits(sizes, 5, tokens, first, 6, query_block=8, expert_chunk=3)
    key = ref.seed_key(5)
    outer = ref.outer_weights(key, sizes)
    x = outer["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    for li in range(sizes["n_layers"]):
        x = ref.layer(x, ref.layer_weights(key, li, sizes), sizes, li)
    want = ref.head(x, outer, jnp.asarray(first), 6, sizes, False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    # the window clips: a token 9 back moves no window layer's row, but
    # the full layers still see it
    assert ref.window_of(0) == 0 and ref.window_of(1) == 8


def test_what_the_new_kernels_must_do_is_counted_from_true_lengths():
    kern = cells.load_module("kernels", "flash_prefill")
    assert kern.pairs(5, 0) == 15 and kern.pairs(5, 8) == 15
    assert kern.pairs(10, 4) == 4 * 5 // 2 + 6 * 4      # 1+2+3+4, then 4 each
    assert kern.pairs(8192, 4096) < 0.76 * kern.pairs(8192, 0)
    for length, window in ((1, 3), (7, 3), (12, 5)):
        assert kern.pairs(length, window) == sum(
            min(p + 1, window) for p in range(length))


def test_a_toy_run_through_the_adapter_and_the_reference(capsys):
    rc = bench_run.main(
        ["--workload", "tiny-prerouted.batch", "--seed", str(2**31 + 11),
         "--seconds", "2", "--trace", "0"], devices=cpu_devices, bench=BENCH)
    out, _ = capsys.readouterr()
    result = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 3 and result["compiles_in_window"] == 0
    assert set(result["metrics"]) == {"tpot_mean_ms", "tokens_per_s", "setup_s"}
    assert result["numbers"]["health_flips"] == [0, 0]


def point_a_ring_page_at_a_neighbours(system):
    """A planted cache fault: every even slot's second ring page is its
    odd neighbour's, so two requests write and read the same window rows."""
    batcher = system.engine._batcher
    table = np.array(batcher.cache["block_table_win"])
    table[:, 0::2, 1] = table[:, 1::2, 1]
    batcher.cache = dict(batcher.cache, block_table_win=jax.device_put(
        table, batcher.cache["block_table_win"].sharding))


def test_a_ring_page_pointed_at_a_neighbours_is_not_correct(capsys):
    rc = bench_run.main(
        ["--workload", "tiny-prerouted.batch", "--seed", str(2**31 + 12),
         "--seconds", "2", "--trace", "0"], devices=cpu_devices, bench=BENCH,
        tamper=point_a_ring_page_at_a_neighbours)
    out, _ = capsys.readouterr()
    result = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and result["failed"] == 0
    assert result["correct"] is False
    over = {name for name, (value, limit) in result["numbers"].items()
            if value > limit}
    assert over & {"max_gap", "mean_gap"}
