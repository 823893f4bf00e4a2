"""What the families' scope tests share (test_mla_moe / test_window_moe /
test_ssm_hybrid / test_serving_spans): a pass is LOWERED, never compiled
or run, as the batcher's programs call it (``decode.decode_step`` /
``decode.prefill_cache`` inside ``shard_map``), and the op names of the
text (``loc("jit(..)/tdt.attn/qkv/dot_general")``) are held against the
one table of names (``triton_dist_tpu/obs/scopes.py``): every part of the
family's row is there, no ``tdt.`` name is outside the table, and every
matrix product, convolution and kernel call lies under a part. A file
brings its family's toy ``cfg`` / ``params`` / cache spec."""

import dataclasses
import os
import re
import sys

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.models import decode
from triton_dist_tpu.models.tp_transformer import specs_for
from triton_dist_tpu.obs import scopes

PERFBENCH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "perfbench")
if PERFBENCH not in sys.path:
    sys.path.insert(0, PERFBENCH)
# the READER's reduction of an op name: program and reader are held to
# one reading of a name
from harness.scopes import part_of  # noqa: E402

HEAVY = ("dot_general", "conv_general_dilated", "pallas_call")
# what only the step of a family with that part calls: its decode kernel
STEP_ONLY = {"attn": "attn/decode", "retn": "retn/update"}


def _shapes(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _op_names(lowered) -> set:
    """Every name of the text's locations: op names (under ``shard_map``
    over several devices they come without the ``jit(..)/`` in front) and,
    harmlessly, the frames' function and file names."""
    return set(re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True)))


def _out_specs(cfg, first):
    """A layer-plan family's pass returns its counters last."""
    return first + ((P(),) if cfg.pass_counters else ())


def step_names(cfg, params, spec, mesh) -> set:
    """Op names of the lowered decode step."""
    n = mesh.shape[cfg.axis]
    prog = jax.jit(jax.shard_map(
        lambda p, c, t, pos: decode.decode_step(
            cfg, p, c, t, pos, spec=spec),
        mesh=mesh,
        in_specs=(specs_for(cfg, params), spec.specs(cfg), P(), P()),
        out_specs=_out_specs(cfg, (P(), spec.specs(cfg))),
        check_vma=False))
    return _op_names(prog.lower(
        _shapes(params), jax.eval_shape(lambda: spec.init(cfg, n)),
        _i32(cfg.batch), _i32(cfg.batch)))


def admission_names(cfg, params, spec, mesh, s_max: int, bucket: int) -> set:
    """Op names of the lowered admission (``prefill_cache`` with a slot
    mask) at one bucket, the prompt sharded as the batcher shards it."""
    n = mesh.shape[cfg.axis]
    pcfg = dataclasses.replace(cfg, seq=bucket)

    def fn(p, c, prompt, mask, pick):
        prompt_loc = decode._prompt_shard(prompt, cfg.batch, bucket, cfg)
        return decode.prefill_cache(
            pcfg, p, c, prompt_loc, spec, s_max, slot_mask=mask, pick=pick)

    prog = jax.jit(jax.shard_map(
        fn, mesh=mesh,
        in_specs=(specs_for(cfg, params), spec.specs(cfg), P(), P(), P()),
        out_specs=_out_specs(cfg, (spec.specs(cfg), P())),
        check_vma=False))
    return _op_names(prog.lower(
        _shapes(params), jax.eval_shape(lambda: spec.init(cfg, n)),
        _i32(cfg.batch, bucket),
        jax.ShapeDtypeStruct((cfg.batch,), jnp.bool_), _i32(cfg.batch)))


def check_scopes(names: set, row: set) -> None:
    """``names`` carry exactly the scopes of ``row`` (``{"attn",
    "attn/qkv", ..}``), none outside the table, and every heavy op lies
    under a part."""
    found = set()
    for name in names:
        got = part_of(name)
        if got is not None:
            found.update({got[0], "/".join(got)} if got[1] else {got[0]})
            continue
        assert scopes.PREFIX not in name, f"{name}: a tdt. name outside the table"
        if "/" not in name:
            # an op at the top of an interpreted kernel's body comes with no
            # path at all (no ``jit(..)/`` either); its kernel's
            # ``pallas_call`` is what is held to a part
            continue
        assert name.rsplit("/", 1)[-1].rstrip(":") not in HEAVY, (
            f"{name} lies under no part")
    assert found == row, (sorted(found - row), sorted(row - found))
    assert any(n.rsplit("/", 1)[-1] in HEAVY for n in names)


def check_pass(which: str, cfg, params, spec, mesh, s_max: int, row: set,
               bucket: int = 16) -> None:
    """The lowered ``"step"`` or ``"admission"`` of a family against its
    row of the table; only the step calls the decode kernel of a part the
    row has (``STEP_ONLY``)."""
    if which == "step":
        check_scopes(step_names(cfg, params, spec, mesh), row | {
            sub for part, sub in STEP_ONLY.items() if part in row})
    else:
        check_scopes(
            admission_names(cfg, params, spec, mesh, s_max, bucket), row)
