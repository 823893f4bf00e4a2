"""The sparse latent plan's configuration's files: the parameter arithmetic
its file states and the catalog row it is held to, a whole toy run of its
adapter and reference through ``run.py`` (CPU, interpreted kernels,
``tests/tiny_sparse_mla``: an ``index_topk`` smaller than its contexts, a
window smaller than its prompts), its lower-precision control and two
planted faults (a ring page mispointed; the selection replaced by the
first rows) shown not correct. The real configuration's limits are set
from chip readings (``PERF.md``)."""

import json
import os

import jax
import numpy as np

import control
import run as bench_run
from harness import cells, correct, traffic
from harness.stats import Record

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = cells.load_json(os.path.join(HERE, "tiny_sparse_mla", "BENCHMARK.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def cpu_devices(cell):
    return jax.devices()[: cell.chips]


def blocks(c: dict) -> dict:
    """Parameters of each block from the published keys, norms left out."""
    h = c["hidden_size"]

    def attention(pre: str, heads: str) -> int:
        nh, rq, rkv = c[heads], c[pre + "q_lora_rank"], c[pre + "kv_lora_rank"]
        nope, rope = c[pre + "qk_nope_head_dim"], c[pre + "qk_rope_head_dim"]
        dv = c[pre + "v_head_dim"]
        return (h * rq + rq * nh * (nope + rope) + h * (rkv + rope)
                + rkv * nh * (nope + dv) + nh * dv * h + h * nh)

    indexer = (c["q_lora_rank"] * c["index_n_heads"] * c["index_head_dim"]
               + h * c["index_head_dim"] + h * c["index_n_heads"])
    expert = 3 * h * c["moe_intermediate_size"]
    return dict(
        full=attention("", "num_attention_heads") + indexer,
        window=attention("swa_", "swa_num_attention_heads"),
        dense=3 * h * c["intermediate_size"], expert=expert,
        shared=expert * c["n_shared_experts"])


def test_the_configuration_files_parameter_arithmetic_and_catalog_row():
    bench = cells.benchmark()
    cell = cells.Cell(bench, "dots3-note-prev-ep8.doc-reason")
    c = cell.config
    pub = dict(c, **c["published"])
    b = blocks(pub)
    assert abs(b["full"] / 144.1e6 - 1) < 1e-3
    assert abs(b["window"] / 90.8e6 - 1) < 1e-3
    router = pub["hidden_size"] * pub["n_routed_experts"]
    held = c["experts_held"][1]
    layers = [b[kind] + (b["dense"] if li < c["first_k_dense_replace"] else
                         held * b["expert"] + b["shared"] + router)
              for li, kind in enumerate(
                  "full" if t == "full_attention" else "window"
                  for t in c["layer_types"][: c["n_layers"]])]
    total = sum(layers) + 2 * c["vocab"] * c["hidden"]
    assert abs(total / 4087e6 - 1) < 1e-3 and "4,087 M" in c["held"]["parameters"]
    assert c["layer_types"][:5] == ["full_attention"] * 2 + ["sliding_attention"] * 3
    assert c["layer_types"].count("full_attention") == 13
    # every number of the catalog row stands under its own key, but the
    # experts held; the cuts are listed
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "dots3-note-prev")
    assert c["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if c.get(k) != v]
    assert differs == ["n_routed_experts"] and c["n_routed_experts"] == held == 32
    assert c["published"]["n_routed_experts"] == 256
    assert c["reduced"] == ["n_layers", "n_routed_experts", "vocab"]
    assert (c["n_layers"], c["vocab"], c["vocab"] * 8) == (5, 19008, c["vocab_size"])
    assert c["hidden"] == c["hidden_size"] and c["ffn"] == c["intermediate_size"]
    assert (c["n_q_heads"], c["head_dim"], c["rope_theta"]) == (
        c["num_attention_heads"], c["qk_rope_head_dim"], 80000000)
    # what the program is built from
    adapter = cells.load_module("programs", c["program"])
    cfg = adapter.model_config(c)
    full, win = cfg.geometry("full"), cfg.geometry("window")
    assert (full.n_heads, full.head_dim, full.v, full.row, full.indexed) == (
        128, 192, 128, 640, True)
    assert (win.n_heads, win.head_dim, win.v, win.row, win.window) == (
        64, 256, 128, 1152, 513)
    assert (cfg.n_experts, cfg.held, cfg.topk, cfg.expert_ffn, cfg.ffn,
            cfg.index_topk, cfg.index_n_heads, cfg.index_head_dim) == (
        256, (0, 32), 8, 1536, 13824, 2048, 64, 128)
    # the cell's traffic: one round of the slots, one prefill bucket, every
    # context past the index's top-k and the window
    reqs = traffic.generate(traffic.load(cell.traffic_path), c["vocab"], 1,
                            bench["run_seconds"])
    assert len(reqs) == c["engine"]["slots"] == 32
    assert min(len(r.prompt) for r in reqs) > c["index_topk"] > c["sliding_window_size"]
    assert max(len(r.prompt) for r in reqs) <= 8192
    assert max(int(t) for r in reqs for t in r.prompt) < c["vocab"]


def _run(workload: str, seed: int, capsys, tamper=None) -> dict:
    rc = bench_run.main(
        ["--workload", workload, "--seed", str(seed), "--seconds", "2",
         "--trace", "0"], devices=cpu_devices, bench=BENCH, tamper=tamper)
    out, _ = capsys.readouterr()
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1])


def test_a_toy_run_through_the_adapter_and_the_reference(capsys):
    result = _run("tiny-sparse-mla.batch", 2**31 + 11, capsys)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 3 and result["compiles_in_window"] == 0
    assert set(result["metrics"]) == {"tpot_mean_ms", "tokens_per_s", "setup_s"}
    assert result["numbers"]["health_flips"] == [0, 0]


def point_a_ring_page_elsewhere(system):
    """A planted cache fault: every slot's second ring page is slot 0's,
    so the requests write and read the same ring rows."""
    batcher = system.engine._batcher
    table = np.array(batcher.cache["block_table_win"])
    table[:, :, 1] = table[:, :1, 1]
    batcher.cache = dict(batcher.cache, block_table_win=jax.device_put(
        table, batcher.cache["block_table_win"].sharding))


def select_the_first_rows(system):
    """A planted selection fault: a step attends the FIRST ``index_topk``
    rows of a slot whatever the indexer scored."""
    from triton_dist_tpu.models.decode import LatentPagedCacheSpec
    from triton_dist_tpu.ops import sparse_index

    def first_rows(scores, topk):
        import jax.numpy as jnp

        return jnp.isfinite(scores) & (jnp.arange(scores.shape[1]) < topk)

    step = LatentPagedCacheSpec.write_and_attend

    def faulty_step(self, *args, **kw):
        # the step looks the selection up when it is traced; an admission
        # (``selection_mask``) keeps the sound one
        sound, sparse_index.topk_mask = sparse_index.topk_mask, first_rows
        try:
            return step(self, *args, **kw)
        finally:
            sparse_index.topk_mask = sound

    LatentPagedCacheSpec.write_and_attend = faulty_step


def _over(result: dict) -> set:
    return {name for name, (value, limit) in result["numbers"].items()
            if value > limit}


def test_a_ring_page_mispointed_is_not_correct(capsys):
    result = _run("tiny-sparse-mla.batch", 2**31 + 12, capsys,
                  tamper=point_a_ring_page_elsewhere)
    assert result["failed"] == 0 and result["correct"] is False
    assert _over(result) == {"max_gap", "mean_gap"}


def test_the_first_rows_for_the_selection_is_not_correct(capsys, monkeypatch):
    from triton_dist_tpu.models.decode import LatentPagedCacheSpec
    from triton_dist_tpu.ops import common

    monkeypatch.setattr(LatentPagedCacheSpec, "write_and_attend",
                        LatentPagedCacheSpec.write_and_attend)
    common._jit_cache.clear()
    common._wrapper_cache.clear()
    try:
        result = _run("tiny-sparse-mla.batch", 2**31 + 13, capsys,
                      tamper=select_the_first_rows)
    finally:    # the step traced with the fault must serve no later test
        common._jit_cache.clear()
        common._wrapper_cache.clear()
    assert result["failed"] == 0 and result["correct"] is False
    assert _over(result) == {"max_gap", "mean_gap"}


def test_the_selection_counted_against_the_references(capsys):
    """``tools/selection_diff.py`` on the toy: the program's selections,
    tapped at its admissions and steps, hold no key the reference's do
    not (float32 on both sides), in either indexed layer; the count is
    over rows past ``index_topk`` and every step of the served tokens."""
    import selection_diff
    from triton_dist_tpu.ops import common

    common._jit_cache.clear()
    common._wrapper_cache.clear()
    try:
        rc = selection_diff.main(
            ["--workload", "tiny-sparse-mla.batch", "--seed", str(2**31 + 14),
             "--requests", "2", "--tokens", "6"],
            devices=cpu_devices, bench=BENCH)
    finally:    # programs traced with the taps on must serve no later test
        common._jit_cache.clear()
        common._wrapper_cache.clear()
    out, _ = capsys.readouterr()
    got = json.loads(out.strip().splitlines()[-1][len("SELECTION-DIFF "):])
    assert rc == 0 and len(got["requests"]) == 2
    for part in ("admission_rows_past_topk", "step_rows"):
        assert [layer["max"] for layer in got[part]] == [0, 0]
    assert [layer["rows"] for layer in got["step_rows"]] == [
        sum(r["tokens"] - 1 for r in got["requests"])] * 2
    assert all(layer["rows"] >= 2 * (11 - 6)
               for layer in got["admission_rows_past_topk"])


def test_the_lower_precision_control_is_not_correct():
    """The reference as W8A8 int8 in the program's place: the token it
    puts first at each position must break a limit, on every seed."""
    cell = cells.Cell(BENCH, "tiny-sparse-mla.control")
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
