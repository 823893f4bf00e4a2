"""The latent-attention / gated-expert configuration's files: the
parameter arithmetic its file states, a whole toy run of its adapter and
reference through ``run.py`` (CPU, interpreted kernels, ``tests/tiny_mla``),
its lower-precision control and a planted wrong-page fault shown not
correct. The real configuration's limits are set from chip readings (``PERF.md``)."""

import json
import os

import jax
import numpy as np

import control
import run as bench_run
from harness import cells, correct, traffic
from harness.stats import Record

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = cells.load_json(os.path.join(HERE, "tiny_mla", "BENCHMARK.json"))


def cpu_devices(cell):
    return jax.devices()[: cell.chips]


def parameters(c: dict, layers: int) -> int:
    """Parameters of ``layers`` layers (the leading dense ones first) with
    embedding and head, from the published keys; norms left out."""
    h, nh = c["hidden_size"], c["num_attention_heads"]
    attn = (h * c["q_lora_rank"]
            + c["q_lora_rank"] * nh * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"])
            + h * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"] * nh * (c["qk_nope_head_dim"] + c["v_head_dim"])
            + nh * c["v_head_dim"] * h)
    expert = 3 * h * c["moe_intermediate_size"]
    moe = attn + h * c["n_routed_experts"] + c["n_routed_experts"] + (
        c["n_routed_experts"] + c["n_shared_experts"]) * expert
    dense = attn + 3 * h * c["intermediate_size"]
    k = c["first_k_dense_replace"]
    return k * dense + (layers - k) * moe + 2 * c["vocab_size"] * h


def test_the_configuration_files_parameter_arithmetic():
    bench = cells.benchmark()
    cell = cells.Cell(bench, "joyai-llm-flash.reason")
    c = cell.config
    assert abs(parameters(c, c["num_hidden_layers"]) / 48.94e9 - 1) < 1e-3
    assert abs(parameters(c, c["n_layers"]) / 5558e6 - 1) < 1e-3
    assert "48.94 B" in c["published"]["parameters"]
    assert "5558 M" in c["held"]["parameters"]
    # the published keys stand at the top level under their own names
    for key, value in c["published"].items():
        if key != "parameters":
            assert c[key] == value, key
    assert c["reduced"] == ["n_layers"] and c["n_layers"] == 5
    assert c["hidden"] == c["hidden_size"] and c["ffn"] == c["intermediate_size"]
    assert c["vocab"] == c["vocab_size"] and c["norm_eps"] == c["rms_norm_eps"]
    assert c["qk_head_dim"] == c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    # what the program is built from
    adapter = cells.load_module("programs", c["program"])
    cfg = adapter.model_config(c)
    assert (cfg.head_dim, cfg.latent_row, cfg.n_experts, cfg.topk,
            cfg.expert_ffn) == (192, 640, 256, 8, 768)
    # the cell's traffic: three rounds of the slots, one prefill bucket
    reqs = traffic.generate(traffic.load(cell.traffic_path), c["vocab"], 1,
                            bench["run_seconds"])
    assert len(reqs) == 3 * c["engine"]["slots"]
    assert max(len(r.prompt) for r in reqs) <= 256 < min(
        len(r.prompt) for r in reqs) * 2


def test_a_toy_run_through_the_adapter_and_the_reference(capsys):
    rc = bench_run.main(
        ["--workload", "tiny-mla.batch", "--seed", str(2**31 + 11),
         "--seconds", "2", "--trace", "0"], devices=cpu_devices, bench=BENCH)
    out, err = capsys.readouterr()
    result = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 3 and result["compiles_in_window"] == 0
    assert set(result["metrics"]) == {"tpot_mean_ms", "tokens_per_s", "setup_s"}
    assert result["numbers"]["health_flips"] == [0, 0]


def hand_a_page_out_twice(system):
    """A planted cache fault: every even slot's second logical page is
    its odd neighbour's physical page, so two requests write and read the
    same latent rows."""
    batcher = system.engine._batcher
    table = np.array(batcher.cache["block_table"])
    table[:, 0::2, 1] = table[:, 1::2, 1]
    batcher.cache = dict(batcher.cache, block_table=jax.device_put(
        table, batcher.cache["block_table"].sharding))


def test_a_page_handed_out_twice_is_not_correct(capsys):
    rc = bench_run.main(
        ["--workload", "tiny-mla.batch", "--seed", str(2**31 + 12),
         "--seconds", "2", "--trace", "0"], devices=cpu_devices, bench=BENCH,
        tamper=hand_a_page_out_twice)
    out, _ = capsys.readouterr()
    result = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and result["failed"] == 0
    assert result["correct"] is False
    over = {name for name, (value, limit) in result["numbers"].items()
            if value > limit}
    assert over == {"max_gap", "mean_gap"}


def test_the_lower_precision_control_is_not_correct():
    """The reference as W8A8 int8 in the program's place: the token it
    puts first at each position must break a limit, on every seed."""
    cell = cells.Cell(BENCH, "tiny-mla.control")
    sizes, spec = cell.config["sizes"], traffic.load(cell.traffic_path)
    reference = cells.load_module("references", cell.config["reference"])
    reference.configure(cell.config)
    dims = correct.shape(spec, spec["check_requests"])
    for seed in (3, 4, 5):
        reqs = traffic.generate(spec, sizes["vocab"], seed, 2.0)
        recs = [Record(r.uid, len(r.prompt), r.n_out,
                       tuple(int(t) for t in np.random.default_rng(seed).integers(
                           0, sizes["vocab"], r.n_out)),
                       0.0, 0.0, 0.1, 1.0) for r in reqs]
        picked = correct.sample(recs, seed, dims[0])
        got = correct.judge(reference, sizes, seed, picked,
                            {r.uid: r.prompt for r in reqs}, dims, None,
                            control=True)
        seen = control.verdicts(dict(got, seed=seed), cell.config["limits"])
        assert seen["control_correct"] is False
        assert set(seen["control_over_limit"]) & {"max_gap", "mean_gap"}
