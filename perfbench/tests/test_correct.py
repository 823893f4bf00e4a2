"""The comparison that decides ``correct``, shown to fail: the
lower-precision control at a size a test run can hold, and a whole run
with the timed path broken underneath. CPU, interpreted kernels, the toy
configuration under ``tests/tiny``; the limits for the real
configurations are set from chip readings (``PERF.md``)."""

import json
import os

import jax
import numpy as np
import pytest

import control
import run as bench_run
from harness import cells, correct, traffic
from harness.stats import Record

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = cells.load_json(os.path.join(HERE, "tiny", "BENCHMARK.json"))


def cpu_devices(cell):
    return jax.devices()[: cell.chips]


def drive(capsys, workload, seed, tamper=None, trace=0):
    rc = bench_run.main(
        ["--workload", workload, "--seed", str(seed), "--seconds", "2",
         "--trace", str(trace)],
        devices=cpu_devices, bench=BENCH, tamper=tamper)
    out, err = capsys.readouterr()
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1]), err


def test_a_sound_run_is_correct_and_prints_the_contract_line(capsys):
    result, err = drive(capsys, "tiny.batch", 2**31 + 7)
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(result)[-1] == "numbers"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 3 and result["compiles_in_window"] == 0
    assert set(result["metrics"]) == {"tpot_mean_ms", "tokens_per_s", "setup_s"}
    for name, (value, limit) in result["numbers"].items():
        assert f"perfbench compared {name}: {value} (limit {limit})" in err
    assert err.strip().splitlines()[-1].startswith("perfbench compared")


def test_the_lower_precision_control_is_not_correct():
    """The reference, put in the program's place and computed as W8A8
    int8: at every position of the same prompts and tokens, the token it
    puts first is judged like a served one, and must break a limit."""
    cell = cells.Cell(BENCH, "tiny.control")
    sizes, spec = cell.config["sizes"], traffic.load(cell.traffic_path)
    reference = cells.load_module("references", cell.config["reference"])
    dims = correct.shape(spec, spec["check_requests"])
    fails, rows = 0, []
    for seed in (3, 4, 5):
        reqs = traffic.generate(spec, sizes["vocab"], seed, 2.0)
        # any tokens will do as the "served" ones: the control is judged
        # on the tokens IT puts first at each of those positions
        recs = [Record(r.uid, len(r.prompt), r.n_out,
                       tuple(int(t) for t in np.random.default_rng(seed).integers(
                           0, sizes["vocab"], r.n_out)),
                       0.0, 0.0, 0.1, 1.0) for r in reqs]
        picked = correct.sample(recs, seed, dims[0])
        got = correct.judge(reference, sizes, seed, picked,
                            {r.uid: r.prompt for r in reqs}, dims, None,
                            control=True)
        seen = control.verdicts(dict(got, seed=seed), cell.config["limits"])
        fails += seen["control_correct"] is False
        assert set(seen["control_over_limit"]) & {"max_gap", "mean_gap"}
        assert got["control_max_gap"] > 3 * cell.config["limits"]["max_gap"]
        rows.append(dict(got, seed=seed))
    assert fails == 3
    # the tool's exit code: 1 as soon as one control seed would pass, or
    # a sound seed would not, under the limits it is given
    sound = [dict(r, max_gap=0.0, mean_gap=0.0) for r in rows]
    assert control.summarize(sound, cell.config["limits"]) == 0
    assert control.summarize(sound, {"max_gap": 1e9, "mean_gap": 1e9}) == 1
    assert control.summarize(rows, cell.config["limits"]) == 1


def test_the_control_tool_end_to_end(capsys):
    """``control.py`` as it runs on the chip, at toy size: the program's
    seeds come out correct and the control's do not, so it exits 0."""
    rc = control.main(
        ["--workload", "tiny.control", "--seeds", "31,32", "--seconds", "2",
         "--control-seeds", "2"], devices=cpu_devices, bench=BENCH)
    out, _ = capsys.readouterr()
    lines = [ln for ln in out.splitlines() if ln.startswith("CONTROL-VERDICT")]
    assert rc == 0 and len(lines) == 2
    assert all(json.loads(ln.split(" ", 1)[1])["control_correct"] is False
               for ln in lines)


def flip_a_token(system):
    """Break the timed path where a token is produced: after a decode
    round, every live slot's newest token is replaced (and fed back, as
    the program would feed its own)."""
    batcher = system.engine._batcher
    inner = batcher._decode_round

    def broken():
        inner()
        for i, req in enumerate(batcher.slot_req):
            if req is not None and batcher.slot_out[i]:
                wrong = (batcher.slot_out[i][-1] + 1) % system.vocab
                batcher.slot_out[i][-1] = wrong
                batcher.tok[i] = wrong

    batcher._decode_round = broken


def test_a_run_with_the_timed_path_broken_is_not_correct(capsys):
    result, err = drive(capsys, "tiny.batch", 11, tamper=flip_a_token)
    assert result["correct"] is False
    value, limit = result["numbers"]["max_gap"]
    assert value > limit
    assert f"perfbench compared max_gap: {value} (limit {limit})" in err


def test_a_request_that_does_not_finish_whole_is_not_correct(capsys):
    def drop_a_token(system):
        batcher = system.engine._batcher
        inner = batcher.drain_finished

        def short():
            done = inner()
            return [(uid, toks[:-1] if str(uid)[1:].isdigit() else toks)
                    for uid, toks in done]

        batcher.drain_finished = short

    result, _ = drive(capsys, "tiny.batch", 12, tamper=drop_a_token)
    assert result["correct"] is False and result["failed"] > 0
    assert result["numbers"]["failed"][0] > 0


def test_no_tpu_no_result(capsys):
    """The real entry refuses a machine without the chip (this one)."""
    with pytest.raises(SystemExit) as e:
        bench_run.main(["--workload", "tiny.batch", "--seed", "1",
                        "--seconds", "1"], bench=BENCH)
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out.strip() == ""


def test_four_virtual_devices_walk_the_sharded_path(capsys):
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    result, _ = drive(capsys, "tiny-tp4.batch", 21)
    assert result["correct"] is True and result["device"]["count"] == 4
