"""The window-attention / gated-expert configuration's files: the
parameter arithmetic its file states and the cell's traffic, a whole toy
run of its adapter and reference through ``run.py`` (CPU, interpreted
kernels, ``tests/tiny_window``: a SHARE of the bank and of the vocabulary,
lookahead on) and a planted ring fault shown not correct. The real
configuration's limits are set from chip readings (``PERF.md``)."""

import json
import os

import jax
import numpy as np

import run as bench_run
from harness import cells, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = cells.load_json(os.path.join(HERE, "tiny_window", "BENCHMARK.json"))
CELL = "k-exaone-236b-a23b-ep8.reason-long"


def cpu_devices(cell):
    return jax.devices()[: cell.chips]


def parameters(c: dict, layers: int, experts: int, vocab: int) -> int:
    """Parameters of ``layers`` layers (the leading dense ones first) with
    ``experts`` routed experts a layer and ``vocab`` rows of embedding and
    head, from the published keys."""
    h, d = c["hidden_size"], c["head_dim"]
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    attn = 2 * h * hq * d + 2 * h * hkv * d + 2 * d      # q, o, k, v, q/k norms
    expert = 3 * h * c["moe_intermediate_size"]
    router = h * c["published"]["num_experts"] + c["published"]["num_experts"]
    moe = attn + router + (experts + c["num_shared_experts"]) * expert + 2 * h
    dense = attn + 3 * h * c["intermediate_size"] + 2 * h
    k = c["first_k_dense_replace"]
    return k * dense + (layers - k) * moe + 2 * vocab * h + h


def test_the_configuration_files_parameter_arithmetic():
    bench = cells.benchmark()
    cell = cells.Cell(bench, CELL)
    c = cell.config
    pub = c["published"]
    whole = parameters(c, pub["num_hidden_layers"], pub["num_experts"],
                       pub["vocab_size"])
    assert abs(whole / 236.57e9 - 1) < 1e-4
    held = parameters(c, c["n_layers"], c["num_experts"], c["vocab"])
    assert abs(held / 3712e6 - 1) < 1e-4
    assert "236.57 B" in pub["parameters"] and "23.67 B" in pub["parameters"]
    assert "3712 M" in c["held"]["parameters"]
    # the published keys stand at the top level under their own names, but
    # for the one that counts the experts held
    for key, value in pub.items():
        if key not in ("parameters", "num_experts"):
            assert c[key] == value, key
    entry = {e["name"]: e for e in bench["configs"]}[c["name"]]
    assert entry["reduced"] == c["reduced"] == ["n_layers", "num_experts", "vocab"]
    assert (c["n_layers"], c["num_experts"], c["vocab"]) == (5, 16, 19200)
    assert (pub["num_experts"], pub["vocab_size"]) == (128, 153600)
    assert c["experts_held"] == [0, 16] and c["vocab_held"] == [0, 19200]
    assert c["num_experts"] * 8 == pub["num_experts"]
    assert c["vocab"] * 8 == pub["vocab_size"]
    # no width is cut
    assert (c["hidden"], c["ffn"], c["n_q_heads"], c["n_kv_heads"],
            c["head_dim"]) == (6144, 18432, 64, 8, 128)
    assert (c["hidden"], c["ffn"]) == (c["hidden_size"], c["intermediate_size"])
    assert (c["moe_intermediate_size"], c["num_experts_per_tok"],
            c["sliding_window"]) == (2048, 8, 128)
    assert c["rope_theta"] == c["rope_parameters"]["rope_theta"]
    assert c["norm_eps"] == c["rms_norm_eps"]
    # what the program is built from: one whole LLLG period, the leading
    # dense layer in it, the router at its published width
    adapter = cells.load_module("programs", c["program"])
    cfg = adapter.model_config(c)
    assert cfg.layer_types == ("window", "window", "window", "full", "window")
    assert (cfg.window, cfg.n_experts, cfg.topk, cfg.expert_ffn, cfg.held,
            cfg.vocab_held) == (128, 128, 8, 2048, (0, 16), (0, 19200))
    assert cfg.first_k_dense == 1 and cfg.cache_kind == "kv_window"


def test_the_cells_traffic_is_one_round_of_the_slots():
    bench = cells.benchmark()
    cell = cells.Cell(bench, CELL)
    c = cell.config
    spec = traffic.load(cell.traffic_path)
    assert {k: v for k, v in spec.items() if not k.startswith("_")} == {
        "process": "backlog", "backlog_tokens_per_s": 910.3,
        "prompt_len": {"uniform": [129, 256]},
        "output_len": {"uniform": [1024, 1536]},
        "temperature": 0.0, "check_requests": 3}
    reqs = traffic.generate(spec, c["vocab"], 2**31 + 5, bench["run_seconds"])
    work = traffic.work(reqs)
    assert work["requests"] == 32 == c["engine"]["slots"]
    assert (work["prompt_tokens"], work["output_tokens"]) == (6176, 40960)
    assert all(r.t_s == 0.0 for r in reqs)
    # ids from the slice of the vocabulary held; every context fits
    assert max(max(r.prompt) for r in reqs) < c["vocab"]
    assert max(len(r.prompt) + r.n_out for r in reqs) <= c["engine"]["s_max"]
    assert cell.chips == 1 and set(cell.end_to_end) == {
        "tpot_mean_ms", "tokens_per_s", "setup_s"}
    assert {"step.window_moe_decode_roofline", "kernel.window_decode_roofline",
            "kernel.held_expert_gemm_roofline", "moe.held_experts_hit_per_layer",
            "attn.window_rows_share", "batcher.tokens_per_step",
            "step.decode_device_ms", "device.idle_share"} == set(cell.per_layer)


def test_a_toy_run_through_the_adapter_and_the_reference(capsys):
    rc = bench_run.main(
        ["--workload", "tiny-window.batch", "--seed", str(2**31 + 11),
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
        ["--workload", "tiny-window.batch", "--seed", str(2**31 + 12),
         "--seconds", "2", "--trace", "0"], devices=cpu_devices, bench=BENCH,
        tamper=point_a_ring_page_at_a_neighbours)
    out, _ = capsys.readouterr()
    result = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and result["failed"] == 0
    assert result["correct"] is False
    over = {name for name, (value, limit) in result["numbers"].items()
            if value > limit}
    assert over & {"max_gap", "mean_gap"}
