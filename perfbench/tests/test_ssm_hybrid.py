"""The state-space / attention configuration's files: the cell's files
through ``cells.Cell``, the published keys and the parameter count, the
cell's traffic, the four readers this configuration brings on a small
hand-made trace (``fixtures/ssm_small.json``), how far back the
reference's state remembers, a whole toy run of the adapter and reference
through ``run.py`` (CPU, interpreted kernels, ``tests/tiny_ssm``: two
rounds of the slots, lookahead on) and the two planted state faults shown
not correct. The real configuration's limits are set from chip readings
(``PERF.md``)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run as bench_run
from harness import cells, spans as sp, stats, trace as tr, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = cells.load_json(os.path.join(HERE, "tiny_ssm", "BENCHMARK.json"))
CELL = "ai21-jamba2-3b.reason-wide"
NEW_METRICS = {"step.ssm_decode_roofline", "kernel.state_update_roofline",
               "kernel.selective_scan_roofline", "ssm.state_bytes_share"}


def cpu_devices(cell):
    return jax.devices()[: cell.chips]


def test_the_configuration_file_holds_the_catalog_rows_keys_uncut():
    bench = cells.benchmark()
    cell = cells.Cell(bench, CELL)
    c = cell.config
    row = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(row):
        with open(row) as f:
            rows = [json.loads(line) for line in f]
        (pub,) = [r for r in rows if r["name"] == "AI21-Jamba2-3B"]
        assert c["source"] == pub["source_url"]
        for key, value in pub["config"].items():
            assert c[key] == value, key
    entry = {e["name"]: e for e in bench["configs"]}[c["name"]]
    assert entry["reduced"] == c["reduced"] == []
    assert (c["hidden"], c["ffn"], c["n_layers"], c["n_q_heads"],
            c["n_kv_heads"], c["head_dim"], c["vocab"]) == (
        c["hidden_size"], c["intermediate_size"], c["num_hidden_layers"],
        c["num_attention_heads"], c["num_key_value_heads"], 128,
        c["vocab_size"]) == (2560, 8192, 28, 20, 1, 128, 65536)
    assert c["rope_theta"] is None and c["norm_eps"] == c["rms_norm_eps"]
    assert "lookahead" not in c["engine"]       # the batcher's own default
    assert c["engine"] == {"slots": 64, "s_max": 2048, "page": 128,
                           "max_queue": 4096}
    for key in ("head_dim", "inner_norms", "rope_theta", "state_dtype",
                "weights", "layer_order"):
        assert key in c["assumed"], key
    reference = cells.load_module("references", c["reference"])
    reference.configure(c)
    assert reference.count_parameters(c["sizes"]) == 3_029_337_472
    assert "3,029,337,472" in c["parameters"]
    assert [li for li in range(28) if reference.is_attention(li)] == [7, 21]
    adapter = cells.load_module("programs", c["program"])
    cfg = adapter.model_config(c)
    assert (cfg.cache_kind, cfg.batch, cfg.d_inner) == ("kv_state", 64, 5120)
    assert cfg.state_bytes() == 64 * 19_169_280


def test_the_cells_traffic_is_two_rounds_of_the_slots():
    bench = cells.benchmark()
    cell = cells.Cell(bench, CELL)
    c = cell.config
    spec = traffic.load(cell.traffic_path)
    assert {k: v for k, v in spec.items() if not k.startswith("_")} == {
        "process": "backlog", "backlog_tokens_per_s": 1820.5,
        "prompt_len": {"uniform": [129, 512]},
        "output_len": {"uniform": [512, 768]},
        "temperature": 0.0, "check_requests": 3}
    reqs = traffic.generate(spec, c["vocab"], 2**31 + 5, bench["run_seconds"])
    work = traffic.work(reqs)
    assert work["requests"] == 128 == 2 * c["engine"]["slots"]
    assert work["output_tokens"] == 81920
    assert 40_000 < work["prompt_tokens"] < 42_000
    assert all(r.t_s == 0.0 for r in reqs)
    assert max(len(r.prompt) + r.n_out for r in reqs) <= c["engine"]["s_max"]
    # two prefill buckets
    assert {256, 512} == {1 << (len(r.prompt) - 1).bit_length() for r in reqs}
    assert cell.chips == 1 and set(cell.end_to_end) == {
        "tpot_mean_ms", "tokens_per_s", "setup_s"}
    assert set(cell.per_layer) == NEW_METRICS | {
        "batcher.tokens_per_step", "step.decode_device_ms", "device.idle_share"}


def _fixture_run():
    cell = cells.Cell(cells.benchmark(), CELL)
    fx = cells.load_json(os.path.join(HERE, "fixtures", "ssm_small.json"))
    t = tr.Trace.from_json(fx["trace"])
    adapter = cells.load_module("programs", cell.config["program"])
    records = [stats.Record("w0", 200, 600, tuple(range(600)), 0.0, 0.0, 0.02, 9.0),
               stats.Record("w1", 400, 600, tuple(range(600)), 0.0, 0.0, 0.05, 9.0)]
    run = bench_run.Run(
        cell=cell, config=cell.config, sizes=cell.config["sizes"],
        records=records, t_open=0.0, seconds=1.0, setup_s=1.0, chips=1,
        weight_bytes=6.06e9, prefill_rows={"w0": 256, "w1": 512},
        peaks=cells.peaks("TPU v5 lite"), programs=adapter.PROGRAMS,
        trace=t, plane="/device:TPU:0", window=t.window())
    run.tdt_spans = sp.Spans.from_json(fx["spans"])
    return run, fx["expect"]


def test_the_four_readers_on_the_hand_made_trace():
    """Each against the arithmetic written out in the fixture's ``_how``;
    the list-less readers find the same programs; none passes 100%."""
    run, want = _fixture_run()
    for name in sorted(NEW_METRICS | {"step.decode_device_ms",
                                      "step.prefill_device_ms"}):
        got = cells.load_module("metrics", name).read(run)
        assert got == pytest.approx(want[name], rel=1e-9), name
        assert got < 100.0
    kern = run.kernel("ssm_decode_step")
    assert (kern.mamba_layers(run), kern.state_bytes_per_slot(run),
            kern.row_bytes(run)) == (26, 19_169_280, 512)
    assert kern.per_round(run) == (64.0, 76928.0)


def test_the_readers_read_nothing_where_the_program_writes_no_counter():
    """A parent commit's run (no ``state_slots`` on the round, no such
    kernel in the trace): nothing, and no error."""
    run, _ = _fixture_run()
    for s in run.tdt_spans.all:
        s.stats.pop("state_slots", None)
    ops = run.trace.planes["/device:TPU:0"][tr.OPS]
    ops.names = [n.replace("selective_", "other_") for n in ops.names]
    for name in NEW_METRICS:
        assert cells.load_module("metrics", name).read(run) is None, name


def test_how_far_back_the_state_remembers():
    """The reading the reference's docstring states, at toy size through
    four state-space layers alone (their convolutions alone reach 12
    positions back): with the published initialisation of ``dt`` a token
    changed 24 positions back still moves the last position's logits; with
    ``dt`` of order 1 (``b_dt`` = 1) it does not. Without this a stale
    state or a doubled step would move no logit a few tokens on."""
    cell = cells.Cell(BENCH, "tiny-ssm.batch")
    config = dict(cell.config, attn_layer_period=99, attn_layer_offset=98)
    sizes = config["sizes"]
    ref = cells.load_module("references", "jamba_ssm_hybrid")
    ref.configure(config)
    try:
        key = ref.seed_key(3)
        plain = [ref.layer_weights(key, li, sizes) for li in range(4)]
        outer = ref.outer_weights(key, sizes)
        forgetful = [dict(w, b_dt=jnp.ones_like(w["b_dt"])) for w in plain]

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
            for back in (1, 24):
                deltas = []
                for _ in range(4):
                    tokens = rng.integers(0, sizes["vocab"], 48)
                    other = tokens.copy()
                    other[-1 - back] = (other[-1 - back] + 7) % sizes["vocab"]
                    deltas.append(float(jnp.abs(
                        last_logits(layers, jnp.asarray(tokens))
                        - last_logits(layers, jnp.asarray(other))).max()))
                moved[name, back] = float(np.mean(deltas))
        assert moved["published", 24] > 0.05 * moved["published", 1]
        assert moved["forgetful", 24] < 0.001 * moved["forgetful", 1]
    finally:
        ref.configure(cell.config)


def _toy_run(capsys, seed, tamper=None):
    rc = bench_run.main(
        ["--workload", "tiny-ssm.batch", "--seed", str(seed), "--seconds", "2",
         "--trace", "0"], devices=cpu_devices, bench=BENCH, tamper=tamper)
    out, _ = capsys.readouterr()
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1])


def test_a_toy_run_through_the_adapter_and_the_reference(capsys):
    result = _toy_run(capsys, 2**31 + 11)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 6 and result["compiles_in_window"] == 0
    assert set(result["metrics"]) == {"tpot_mean_ms", "tokens_per_s", "setup_s"}
    assert result["numbers"]["health_flips"] == [0, 0]


# -- the two planted faults (``run.main(tamper=)``; the chip runs of PERF.md
# section 4 plant the same two at the real size) --------------------------------

def advance_twice_after_an_admission(system):
    """What a step sent in vain would do if the state were advanced in
    place: in the round after an admission every slot's recurrence state
    is advanced by its token TWICE. Planted from outside: the round's step
    runs once more before its time and the pool's two parity rows change
    places, so that the real step reads the state the extra one wrote."""
    batcher = system.engine._batcher
    admit, inputs = batcher._admit_prefill, batcher._round_inputs
    admitted = []

    def admit_prefill(i, req):
        admitted.append(i)
        return admit(i, req)

    def round_inputs():
        tok_d, pos_d, logits = inputs()
        if admitted and logits is None:
            admitted.clear()
            _, cache = batcher._step(batcher.params, batcher.cache, tok_d, pos_d)
            batcher.cache = dict(cache, ssm=cache["ssm"][:, ::-1])
        return tok_d, pos_d, logits

    batcher._admit_prefill, batcher._round_inputs = admit_prefill, round_inputs


def leave_the_old_state_in_place(system):
    """An admission that writes its pages and NOT its slot's state: the
    slot decodes on from whatever its last request (or nothing) left."""
    from triton_dist_tpu.models.decode import StatePagedKVCacheSpec

    spec = system.engine._batcher.spec
    assert isinstance(spec, StatePagedKVCacheSpec)
    # the spec is frozen and the prefill programs are traced later, at the
    # warm-up: the instance's class is swapped for one whose write is a no-op
    object.__setattr__(spec, "__class__", type(
        "StaleState", (StatePagedKVCacheSpec,),
        {"write_state": lambda self, cache, *a: cache}))


@pytest.mark.parametrize("fault", [advance_twice_after_an_admission,
                                   leave_the_old_state_in_place])
def test_a_planted_state_fault_is_not_correct(capsys, fault):
    result = _toy_run(capsys, 2**31 + 12, tamper=fault)
    assert result["failed"] == 0 and result["correct"] is False
    over = {name for name, (value, limit) in result["numbers"].items()
            if value > limit}
    assert over & {"max_gap", "mean_gap"}
