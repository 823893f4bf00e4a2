"""Self-checks of the reader of the program's own scopes
(``harness/scopes.py``) and of the eight per-layer readers built on it:
the reduction of an ``op_name`` to ``(part, sub-part)``; the decoder of
the xplane's event METADATA against a small xspace that JAX itself
serialises (``jax.profiler.ProfileData`` does not show those stats, which
is why the decoder exists); and two cuts of real traced runs on a v5e
(``fixtures/scopes_recorded.json``: of ``joyai-llm-flash.reason`` one
admission and the two decode steps after it, of
``ai21-jamba2-3b.reason-wide`` two decode steps; the plain form of
``harness/trace.py``, op names cut to ``name shape opcode()``, plus one
scope per distinct op name), where the expected numbers were read off
once (``.expect.json``)."""

import os

import pytest

import run as bench_run
from harness import cells, scopes as sc, trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "fixtures", "scopes_recorded.json")
EXPECT = os.path.join(HERE, "fixtures", "scopes_recorded.expect.json")
READERS = ("step.attn_ms", "step.ffn_ms", "step.ssm_ms", "step.head_ms",
           "step.moe_routing_ms", "step.ssm_glue_ms", "step.unscoped_share",
           "prefill.attn_share")
PLANE, CELL = "/device:TPU:0", "joyai-llm-flash.reason"
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 9 offset_ps: 0 duration_ps: 900000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 1000 duration_ps: 300000 }
    events { metadata_id: 1 offset_ps: 301000 duration_ps: 200000 }
    events { metadata_id: 2 offset_ps: 501000 duration_ps: 300000 }
    events { metadata_id: 3 offset_ps: 801000 duration_ps: 50000
             stats { metadata_id: 8 uint64_value: 7 } } }
  event_metadata { key: 1 value { id: 1
      name: "%fusion.1 = bf16[8,128]{1,0} fusion(bf16[8,64]{1,0} %p0), kind=kLoop"
      stats { metadata_id: 8 uint64_value: 5 }
      stats { metadata_id: 7
              str_value: "jit(decode_step)/tdt.ffn/gate_up/dot_general:" } } }
  event_metadata { key: 2 value { id: 2
      name: "%fusion.2 = bf16[8,64]{1,0} fusion(bf16[8,64]{1,0} %p1), kind=kLoop"
      stats { metadata_id: 7 str_value: "jit(decode_step)/tdt.attn/mul:" } } }
  event_metadata { key: 3 value { id: 3
      name: "%copy-done = bf16[64]{0} copy-done(%copy-start)" } }
  event_metadata { key: 9 value { id: 9 name: "jit_decode_step(123)" } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
  stat_metadata { key: 8 value { id: 8 name: "flops" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "main"
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5 } }
  event_metadata { key: 1 value { id: 1 name: "perfbench.window" } } }
"""


def test_an_op_name_reduces_to_part_and_sub_part():
    assert sc.table is not None
    for name, want in {
        "jit(decode_step)/tdt.attn/qkv/dot_general:": ("attn", "qkv"),
        "jit(fn)/jit(main)/shard_map/tdt.ffn/route/jit(sort)/sort": ("ffn", "route"),
        "tdt.ssm/rsqrt": ("ssm", None),
        # the next segment counts only where the table knows it under THAT part
        "jit(f)/tdt.head/qkv/dot_general": ("head", None),
        "jit(f)/tdt.ffn/experts/group_gemm/pallas_call": ("ffn", "experts"),
        "jit(f)/tdt.attn": ("attn", None),
        # no part, a part the table does not hold, a scope of before PR 37
        "jit(decode_step)/add:": None, "": None,
        "jit(f)/tdt.mlp/dot_general": None,
        "jit(f)/moe_experts/group_gemm/pallas_call": None,
        # only a segment that STARTS with the prefix opens a part
        "jit(f)/transpose(jvp(tdt.attn))/mul": None,
    }.items():
        assert sc.part_of(name) == want, name


@pytest.fixture
def xplane(tmp_path):
    from jax.profiler import ProfileData

    # where a traced run of the cell keeps its file, under the checkout
    path = (tmp_path / ".perfbench" / "trace" / CELL / "plugins" / "profile"
            / "run" / "t.xplane.pb")
    path.parent.mkdir(parents=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    return str(path)


def test_the_decoder_reads_what_jax_does_not_show(xplane):
    """The scope is a stat of the event's METADATA: JAX's reader gives an
    event's own stats only. The decoder folds names in the order
    ``trace.load_xplane`` does, once a distinct name."""
    from jax.profiler import ProfileData

    ops = [ln for pl in ProfileData.from_file(xplane).planes if pl.name == PLANE
           for ln in pl.lines if ln.name == tr.OPS][0]
    assert [dict(e.stats) for e in ops.events] == [{}, {}, {}, {"flops": 7}]
    found = sc.op_names(xplane, PLANE)
    assert [op for _, op in found] == [
        "jit(decode_step)/tdt.attn/mul:",
        "jit(decode_step)/tdt.ffn/gate_up/dot_general:", ""]
    names = tr.load_xplane(xplane).line(PLANE, tr.OPS).names
    assert [tr.short_name(n) for n, _ in found] == names
    scopes = sc.load_xplane(xplane, PLANE, names)
    assert scopes.scopes == [("attn", None), ("ffn", "gate_up"), None]
    assert sc.op_names(xplane, "/device:TPU:7") == []
    with pytest.raises(ValueError, match="not the trace's"):
        sc.load_xplane(xplane, PLANE, names[::-1])


def a_run(trace, scopes, cell=CELL, **kw):
    c = cells.Cell(cells.benchmark(), cell)
    adapter = cells.load_module("programs", c.config["program"])
    window = (0, max(int(ev.end.max()) for ev in trace.planes[PLANE].values()) + 1)
    run = bench_run.Run(cell=c, trace=trace, plane=PLANE, window=window,
                        programs=adapter.PROGRAMS, **kw)
    if scopes is not ...:
        run.tdt_scopes = scopes
    return run


def read_all(run) -> dict:
    return {n: cells.load_module("metrics", n).read(run) for n in READERS}


def test_of_a_run_reads_the_file_once_and_a_foreign_file_as_nothing(
        xplane, tmp_path, monkeypatch, capsys):
    run = a_run(tr.load_xplane(xplane), ...)
    other = a_run(tr.Trace.from_json({PLANE: {tr.OPS: {
        "names": ["op"], "idx": [0], "start": [0], "dur": [5]}}}), ...)
    monkeypatch.setattr(cells, "ROOT", str(tmp_path))
    scopes = sc.of(run)
    assert scopes.scopes == [("attn", None), ("ffn", "gate_up"), None]
    assert sc.of(run) is scopes
    err = capsys.readouterr().err
    assert err.count("2 of 3 distinct device ops carry a tdt.* scope") == 1
    # the table: one execution; 0.6 of 0.85 us under attn, 0.05 unscoped
    assert "decode_step: 1 executions" in err and "tdt.attn" in err
    assert "copy-done bf16[64] x1" in err
    got = read_all(run)
    assert got["step.attn_ms"] == pytest.approx(0.6e-3)
    assert got["step.ffn_ms"] == pytest.approx(0.2e-3)
    assert got["step.unscoped_share"] == pytest.approx(100 * 0.05 / 0.85)
    assert got["step.head_ms"] is None and got["prefill.attn_share"] is None
    # a file whose ops line is not this trace's: a note, and nothing read
    assert sc.of(other) is None and "not read" in capsys.readouterr().err
    assert all(v is None for v in read_all(other).values())


def test_no_scope_reads_as_nothing():
    """A parent commit: the trace is there, no op carries a ``tdt.``
    scope. And a run whose trace is not on disk (the recorded fixture of
    ``test_readers_on_the_recorded_trace``)."""
    ops = {"names": ["fusion.1 bf16[8] fusion(p0)", "copy.2 bf16[8] copy(p1)"],
           "idx": [0, 1], "start": [10, 20], "dur": [5, 5]}
    mods = {"names": ["jit_decode_step(1)"], "idx": [0], "start": [0], "dur": [40]}
    trace = tr.Trace.from_json({PLANE: {tr.MODULES: mods, tr.OPS: ops}})
    assert sc.Scopes.from_json([None, None], ops["names"]) is None
    with pytest.raises(ValueError):
        sc.Scopes.from_json([None], ops["names"])
    assert all(v is None for v in read_all(a_run(trace, None)).values())
    run = a_run(trace, ...)
    run.cell.name = "no-such-cell"
    assert sc.of(run) is None and "tdt_scopes" in run.__dict__
    assert all(v is None for v in read_all(run).values())


def recorded(cell: str):
    cut = cells.load_json(RECORDED)[cell]
    trace = tr.Trace.from_json(cut["trace"])
    names = trace.line(PLANE, tr.OPS).names
    return cut, a_run(trace, sc.Scopes.from_json(cut["scopes"], names), cell)


@pytest.mark.parametrize("cell", ["joyai-llm-flash.reason",
                                  "ai21-jamba2-3b.reason-wide"])
def test_readers_on_the_recorded_cuts(cell):
    """Two decode steps (and, of JoyAI, the admission before them) cut
    from a traced run of the cell on a v5e."""
    cut, run = recorded(cell)
    want = cells.load_json(EXPECT)[cell]
    got = read_all(run)
    assert {n for n, v in got.items() if v is not None} == set(want["metrics"])
    for name, value in want["metrics"].items():
        assert got[name] == pytest.approx(value, rel=1e-9), name
    scopes, steps, ops = sc.inside(run, "decode_step")
    admitted = "prefill.attn_share" in want["metrics"]
    assert len(steps) == 2 and len(run.modules("prefill")) == int(admitted)
    # the identity: the parts and the unscoped time are every op inside the
    # step, each once (a fusion across two parts has ONE scope: its own)
    ms = lambda ev: ev.total_s() / len(steps) * 1e3
    parts = sum(got[f"step.{p}_ms"] or 0.0 for p in ("attn", "ffn", "ssm", "head"))
    assert parts + ms(scopes.unscoped(ops)) == pytest.approx(ms(ops), rel=1e-12)
    assert got["step.unscoped_share"] == pytest.approx(
        100 * ms(scopes.unscoped(ops)) / ms(ops))
    counted = sum(len(scopes.under(ops, p)) for p in sc.table.PARTS)
    assert counted + len(scopes.unscoped(ops)) == len(ops)
    assert ms(ops) == pytest.approx(want["step_ops_ms"], rel=1e-9)
    # the ops fill the program's own time but for the gaps between them; a
    # ``while`` (the alignment's loop) is an event AROUND its body's ops, so
    # the sum counts its time twice and can pass the program's own
    nested = ms(ops.matching("^while"))
    assert 0.9 * ms(steps) < ms(ops) - nested <= ms(steps) * 1.001
    assert (nested > 0) == ("moe_routing" in " ".join(want["metrics"]))
    # sub-parts lie inside their part
    for part, subs in sc.table.PARTS.items():
        whole = scopes.under(ops, part).total_s()
        assert sum(scopes.under(ops, part, s).total_s() for s in subs) <= whole * (1 + 1e-12)
    text = sc.table_text(run)
    assert "decode_step: 2 executions" in text
    assert ("prefill: 1 executions" in text) == admitted
    assert text.count("unscoped") == 1 + admitted
    if "step.ssm_ms" in want["metrics"]:
        scan = sc.part_ms(run, "decode_step", "ssm", "scan")
        assert got["step.ssm_glue_ms"] == pytest.approx(got["step.ssm_ms"] - scan)
        assert 0 < scan < got["step.ssm_ms"]
        calls = scopes.under(ops, "ssm", "scan").matching("^selective_state_update")
        assert len(calls) == 2 * 26 and calls.total_s() > 0.9 * scan * 2e-3
    else:
        assert 0 < got["step.moe_routing_ms"] < got["step.ffn_ms"]
        kernels = scopes.under(ops, "ffn", "experts").matching("^group_gemm")
        assert len(kernels) == 2 * 2 * want["expert_layers"]


def test_the_new_entries_name_readers_with_their_units():
    entries = {m["name"]: m for m in cells.benchmark()["per_layer"]}
    listed = [n for n in READERS if n in entries]
    assert len(listed) >= 6
    for name in listed:
        m = entries[name]
        assert cells.load_module("metrics", name).UNIT == m["unit"]
        assert (m["layer"], m["source"]) == ("model step", "device_trace")
