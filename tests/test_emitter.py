"""The pipeline emitter and the w8 operand-format axis (ISSUE 7).

Three tiers, matching the repo's environment matrix (tests/test_chunked*,
tests/test_ragged.py):

- **host-level** (runs everywhere): the w8 tune-space ordering contract
  (every w8 candidate strictly after its bf16 twin, composed with the
  PR 3/4 chunk and PR 5 ragged orderings), the w8 perf-model terms
  (``estimate_w8_overlap_time_ms`` ≡ the chunked ring model exactly at
  w8=False, w8 halves ONLY the weight term) and the
  ``suggest_w8_overlap`` pruning hook (can never remove a bf16 chunk=1
  candidate), the ``GroupGemmConfig.w8`` axis semantics
  (on-the-fly quantize ≡ the explicit pre-quantized path; loud errors),
  and — through the golden XLA paths every grouped-GEMM entry now serves
  under ``guarded_call`` — the full w8 pipeline numerics (fused overlap ≡
  sequential composition on the same quantized banks).

- **kernel-level** (needs the Mosaic TPU interpreter, jax >= 0.6): the
  MIGRATION CONTRACT — the emitter's generated kernels at each policy
  tuple are BIT-EXACT to verbatim copies of the retired legacy kernel
  bodies (embedded below, frozen at their pre-emitter text), driven
  through the very same host entries by monkeypatching the kernel
  factories. Plus w8-through-the-overlap numerics vs the sequential w8
  composition.

- **chaos**: the w8 ragged chunked pipeline under chunk-signal
  drop/duplication must name only pre-existing diagnostic kinds
  (``chunk_wait`` et al.) or stay exact — the w8 axis adds weight-scale
  DMAs (local HBM) and NO signal edges.
"""

import importlib
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from triton_dist_tpu import config as tdt_config
from triton_dist_tpu import perf_model as pm
# ops/__init__ re-exports functions that shadow these submodule names,
# and `import a.b.c as x` binds through the attribute chain
agg_mod = importlib.import_module("triton_dist_tpu.ops.allgather_group_gemm")
gg_mod = importlib.import_module("triton_dist_tpu.ops.group_gemm")
rs_mod = importlib.import_module("triton_dist_tpu.ops.moe_reduce_rs")
from triton_dist_tpu.ops.group_gemm import (
    GroupGemmConfig,
    group_gemm,
    group_gemm_dw,
    group_gemm_w8,
    quantize_expert_weights,
)
from triton_dist_tpu.ops.moe_utils import (
    moe_align_block_size,
    select_experts,
)
from triton_dist_tpu.resilience import FaultPlan
from triton_dist_tpu.resilience import records as R
from triton_dist_tpu.shmem import device as shmem




def _case_ids():
    """Non-divisor routing: expert counts [5, 0, 12, 1] — a tail of 5, a
    ZERO-row expert, one full block + tail at bm=8, a single-row tail."""
    return jnp.concatenate(
        [
            jnp.zeros(5, jnp.int32),
            jnp.full(12, 2, jnp.int32),
            jnp.full(1, 3, jnp.int32),
        ]
    )


def _w8_like(cfg):
    # both scaled operand formats ride the same pruning hook; only bf16
    # candidates carry the never-pruned guarantee
    return getattr(cfg, "w8", False) or getattr(cfg, "fp8", False)


# ---------------------------------------------------------------------------
# Host tier: tune-space ordering
# ---------------------------------------------------------------------------

def test_w8_tune_space_ordering():
    """Every w8 candidate sits strictly AFTER its bf16 twin in all three
    grouped-GEMM spaces — composed with the chunk invariant (chunked
    strictly after every chunk=1) and the ragged-twin invariant, which
    must keep holding over the w8-extended spaces."""
    from triton_dist_tpu.ops.allgather_group_gemm import (
        AG_GROUP_GEMM_TUNE_SPACE,
    )
    from triton_dist_tpu.ops.grads import TP_MOE_TUNE_SPACE
    from triton_dist_tpu.ops.moe_reduce_rs import MOE_RS_TUNE_SPACE

    for space in (
        TP_MOE_TUNE_SPACE, AG_GROUP_GEMM_TUNE_SPACE, MOE_RS_TUNE_SPACE,
    ):
        assert any(_w8_like(c) for c in space), "space must sweep the axis"
        # the leader stays the proven bf16 padded chunk=1 config
        assert not _w8_like(space[0])
        assert not space[0].ragged and space[0].chunks_per_shard == 1
        for i, c in enumerate(space):
            if _w8_like(c):
                twin = dataclasses.replace(c, w8=False, fp8=False)
                assert twin in space[:i], (
                    f"w8 candidate {c} has no earlier bf16 twin"
                )
            if getattr(c, "fp8", False):
                # ISSUE 19: fp8 sits strictly after its w8 twin too —
                # the admission order is legacy < w8 < fp8
                twin = dataclasses.replace(c, w8=True, fp8=False)
                assert twin in space[:i], (
                    f"fp8 candidate {c} has no earlier w8 twin"
                )
            if c.ragged:
                # PR 5's invariant survives the w8 extension
                twin = dataclasses.replace(c, ragged=False)
                assert twin in space[:i], (
                    f"ragged candidate {c} has no earlier padded twin"
                )
    # the PR 3/4 chunk invariant survives: chunked candidates form a
    # contiguous tail of the pipeline space
    chunked = [c.chunks_per_shard > 1 for c in TP_MOE_TUNE_SPACE]
    fi = chunked.index(True)
    assert all(chunked[fi:]) and not any(chunked[:fi])
    # the w8 composition exists on every axis combination in the pipeline
    # space: plain, ragged, chunked, ragged × chunked
    combos = {
        (c.ragged, c.chunks_per_shard > 1)
        for c in TP_MOE_TUNE_SPACE if _w8_like(c)
    }
    assert combos == {
        (False, False), (True, False), (False, True), (True, True),
    }


# ---------------------------------------------------------------------------
# Host tier: perf model
# ---------------------------------------------------------------------------

def test_w8_overlap_time_model_equivalence():
    """w8=False ≡ the existing chunked ring model plus the full-rate
    weight term, exactly; w8 halves ONLY the weight term."""
    spec = pm.CHIP_SPECS["v5e"]
    sb, wb, n = 8 * 2**20, 512 * 2**20, 8
    for chunks in (1, 2, 4):
        ring = pm.estimate_ring_chunked_time_ms(sb, n, chunks, spec)
        # no weight traffic: the model IS the ring model, w8 irrelevant
        assert pm.estimate_w8_overlap_time_ms(
            sb, n, 0, chunks, w8=False, spec=spec
        ) == ring
        assert pm.estimate_w8_overlap_time_ms(
            sb, n, 0, chunks, w8=True, spec=spec
        ) == ring
        # the weight term rides on top at HBM rate; w8 halves exactly it
        full = pm.estimate_w8_overlap_time_ms(
            sb, n, wb, chunks, w8=False, spec=spec
        )
        half = pm.estimate_w8_overlap_time_ms(
            sb, n, wb, chunks, w8=True, spec=spec
        )
        assert full == pytest.approx(ring + wb / (spec.hbm_gbps * 1e9) * 1e3)
        assert (full - ring) == pytest.approx(2 * (half - ring))
    # world-1: no ring, pure weight stream
    assert pm.estimate_w8_overlap_time_ms(sb, 1, wb, 1, w8=False, spec=spec) \
        == pytest.approx(wb / (spec.hbm_gbps * 1e9) * 1e3)


def test_suggest_w8_overlap():
    """Weight-bound predicate: decode-shaped row counts qualify, prefill/
    training shapes never do; the crossover is E·(flops/HBM)."""
    spec = pm.CHIP_SPECS["v5e"]             # 197 TFLOPS / 819 GB/s ≈ 240
    # decode shape: 256 tokens × topk 2 = 512 rows, 8 experts → ~1924 row
    # crossover: comfortably weight-bound
    assert pm.suggest_w8_overlap(512, 8, spec=spec)
    # bench/prefill shape: 16384 rows is deep into compute-bound
    assert not pm.suggest_w8_overlap(16384, 8, spec=spec)
    # more experts push the crossover out proportionally
    assert pm.suggest_w8_overlap(4096, 64, spec=spec)
    # degenerate input never blows up
    assert pm.suggest_w8_overlap(0, 8, spec=spec)


def test_moe_block_sensible_w8_pruning_never_removes_bf16():
    """The pruning hook prunes w8 candidates on compute-bound problems and
    can NEVER remove a bf16 chunk=1 candidate — swept over shapes."""
    from triton_dist_tpu.ops.grads import TP_MOE_TUNE_SPACE, _moe_block_sensible

    def args_for(m, topk, E, h=32, f=64):
        x = jnp.zeros((m, h), jnp.bfloat16)
        wu = jnp.zeros((E, h, f), jnp.bfloat16)
        wd = jnp.zeros((E, f, h), jnp.bfloat16)
        ids = jnp.tile(jnp.arange(topk, dtype=jnp.int32), (m, 1)) % E
        tw = jnp.zeros((m, topk), jnp.float32)
        return (x, wu, wd, ids, tw)

    # decode shape: w8 survives alongside its bf16 twin
    decode = args_for(256, 2, 8)
    assert _moe_block_sensible(GroupGemmConfig(128, 1024, 512), *decode)
    assert _moe_block_sensible(
        GroupGemmConfig(128, 1024, 512, w8=True), *decode
    )
    # compute-bound shape: w8 pruned, the bf16 twin untouched
    prefill = args_for(65536, 2, 4)
    assert _moe_block_sensible(GroupGemmConfig(128, 1024, 512), *prefill)
    assert not _moe_block_sensible(
        GroupGemmConfig(128, 1024, 512, w8=True), *prefill
    )
    # the safety property, exhaustively over the shipped space: at ANY of
    # these shapes, every bf16 chunk=1 candidate the hook sees survives
    for shape_args in (decode, prefill, args_for(16, 1, 2)):
        for cfg in TP_MOE_TUNE_SPACE:
            if (
                not _w8_like(cfg) and cfg.chunks_per_shard == 1
                and not cfg.ragged and cfg.backend == "pallas"
                and cfg.block_m == 128     # always-viable per the block rule
            ):
                assert _moe_block_sensible(cfg, *shape_args), cfg


# ---------------------------------------------------------------------------
# Host tier: the w8 config axis (golden XLA paths — run everywhere)
# ---------------------------------------------------------------------------

def test_w8_config_axis_matches_explicit_quantization():
    """``GroupGemmConfig(w8=True)`` over a float bank ≡ the explicit
    ``quantize_expert_weights`` + ``group_gemm_w8`` path, identically —
    one knob, one quantizer."""
    ids = _case_ids()
    E, bm = 4, 8
    al = moe_align_block_size(ids, E, bm, ragged=True)
    t_pad = al.sorted_token_ids.shape[0]
    a = jax.random.normal(jax.random.PRNGKey(2), (t_pad, 32), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(3), (E, 32, 64), jnp.float32)
    b_q, sc = quantize_expert_weights(b)
    base = GroupGemmConfig(bm, 64, 32)
    axis_cfg = GroupGemmConfig(bm, 64, 32, w8=True)
    np.testing.assert_array_equal(
        np.asarray(group_gemm(a, b, al.expert_ids, config=axis_cfg)),
        np.asarray(group_gemm_w8(a, b_q, sc, al.expert_ids, config=base)),
    )
    # ragged × w8 composes; dead rows exact zeros, scale folded before mask
    got = np.asarray(group_gemm(
        a, b, al.expert_ids, valid_rows=al.valid_rows,
        config=dataclasses.replace(axis_cfg, ragged=True),
    ))
    ref = np.asarray(group_gemm_w8(
        a, b_q, sc, al.expert_ids, valid_rows=al.valid_rows,
        config=dataclasses.replace(base, ragged=True),
    ))
    np.testing.assert_array_equal(got, ref)
    live = np.asarray(al.sorted_token_ids) < ids.shape[0]
    assert np.all(got[~live] == 0)


def test_w8_errors_and_strips():
    """Loud failure on an int8 bank without scales; the backward strips the
    w8 axis (straight-through) so gradients flow through the float bank."""
    ids = _case_ids()
    E, bm = 4, 8
    al = moe_align_block_size(ids, E, bm)
    t_pad = al.sorted_token_ids.shape[0]
    a = jnp.ones((t_pad, 32), jnp.float32)
    b = jnp.ones((E, 32, 64), jnp.float32)
    b_q, _ = quantize_expert_weights(b)
    with pytest.raises(ValueError, match="scale"):
        group_gemm(
            a, b_q, al.expert_ids, config=GroupGemmConfig(bm, 64, 32, w8=True)
        )
    # group_gemm_grad under a w8 config: the forward quantizes, the
    # backward differentiates against the float bank — db is finite and
    # nonzero (a hard-cut integer boundary would zero it silently)
    from triton_dist_tpu.ops.grads import group_gemm_grad

    def loss(b_):
        out = group_gemm_grad(
            a, b_, al.expert_ids, None, GroupGemmConfig(bm, 64, 32, w8=True),
        )
        return jnp.sum(out.astype(jnp.float32))

    db = jax.grad(loss)(b)
    assert np.isfinite(np.asarray(db)).all()
    assert float(jnp.max(jnp.abs(db))) > 0.0


def test_w8_fused_pipeline_matches_sequential(mesh4):
    """The payoff axis end to end: the overlapped pipeline under
    ``w8=True`` (both fused kernels streaming int8 weights) matches the
    sequential w8 composition on the SAME quantized banks — on this jax
    line through the golden paths, on interpreter/chip lines through the
    real kernels."""
    from triton_dist_tpu.ops.grads import tp_moe_mlp_op

    m_tot, h_dim, f_dim, n_exp, topk = 16, 32, 64, 3, 2
    kx, ku, kd, kl = jax.random.split(jax.random.PRNGKey(77), 4)
    x = jax.random.normal(kx, (m_tot, h_dim), jnp.float32)
    w_up = jax.random.normal(ku, (n_exp, h_dim, f_dim)) / 8
    w_down = jax.random.normal(kd, (n_exp, f_dim, h_dim)) / 8
    tw, ids = select_experts(
        jax.random.normal(kl, (m_tot, n_exp), jnp.float32), topk
    )
    cfg = GroupGemmConfig(4, 32, 32, w8=True)
    fused = tp_moe_mlp_op(
        x, w_up, w_down, ids, tw, mesh4, config=cfg, overlap=True
    )
    seq = tp_moe_mlp_op(
        x, w_up, w_down, ids, tw, mesh4, config=cfg, overlap=False
    )
    bf16 = tp_moe_mlp_op(
        x, w_up, w_down, ids, tw, mesh4,
        config=GroupGemmConfig(4, 32, 32), overlap=False,
    )
    np.testing.assert_allclose(
        np.asarray(fused, np.float32), np.asarray(seq, np.float32),
        rtol=1e-4, atol=1e-4,
    )
    # quantization error is small but real — w8 tracks bf16 loosely
    np.testing.assert_allclose(
        np.asarray(fused, np.float32), np.asarray(bf16, np.float32),
        rtol=0.1, atol=0.1,
    )


def test_ep_moe_w8_int_bank_raises():
    """EPMoEMLP under cfg.w8 rejects int8 banks without scales (same loud
    contract as ops-level resolve_w8 — re-quantizing quantized values
    would silently discard the original scales)."""
    from triton_dist_tpu.layers.ep_moe_mlp import EPMoEMLP

    layer = EPMoEMLP(
        n_experts=4, topk=2, max_m=8, axis="tp",
        gg_config=GroupGemmConfig(4, 32, 16, w8=True),
    )
    w = jnp.ones((4, 16, 32), jnp.float32)
    b_q, _ = quantize_expert_weights(w)
    x = jnp.ones((8, 16), jnp.float32)
    ids = jnp.zeros((8, 2), jnp.int32)
    tw = jnp.full((8, 2), 0.5, jnp.float32)
    with pytest.raises(ValueError, match="scale"):
        layer(x, b_q, b_q.transpose(0, 2, 1), ids, tw)


def test_ep_moe_w8_config_axis(mesh4):
    """EPMoEMLP: ``gg_config.w8`` quantizes the local whole-expert banks
    on the fly — identical to the explicit pre-quantized serving path."""
    from triton_dist_tpu.layers.ep_moe_mlp import EPMoEMLP

    n, m_loc, hidden, ffn, n_exp, topk, max_m = 4, 8, 16, 32, 8, 2, 16
    kx, ki, kw, ku, kd = jax.random.split(jax.random.PRNGKey(51), 5)
    x = jax.random.normal(kx, (n * m_loc, hidden), jnp.float32)
    ids = jax.random.randint(ki, (n * m_loc, topk), 0, n_exp, jnp.int32)
    tw = jax.nn.softmax(
        jax.random.normal(kw, (n * m_loc, topk), jnp.float32), axis=-1
    )
    w_up = jax.random.normal(ku, (n_exp, hidden, ffn)) / 8
    w_down = jax.random.normal(kd, (n_exp, ffn, hidden)) / 8

    def run(cfg, explicit):
        layer = EPMoEMLP(
            n_experts=n_exp, topk=topk, max_m=max_m, axis="tp",
            gg_config=cfg,
        )

        def fn(x, wu, wd, i, t):
            if explicit:
                wq_u, s_u = quantize_expert_weights(wu)
                wq_d, s_d = quantize_expert_weights(wd)
                return layer(
                    x, wq_u, wq_d, i, t, w_up_scale=s_u, w_down_scale=s_d
                )
            return layer(x, wu, wd, i, t)

        from triton_dist_tpu.ops.common import _shard_map

        return jax.jit(
            _shard_map(
                fn, mesh4,
                (P("tp", None), P("tp", None, None),
                 P("tp", None, None), P("tp", None), P("tp", None)),
                P("tp", None),
            )
        )(x, w_up, w_down, ids, tw)

    via_cfg = np.asarray(
        run(GroupGemmConfig(4, 32, 16, w8=True), False), np.float32
    )
    via_scales = np.asarray(
        run(GroupGemmConfig(4, 32, 16), True), np.float32
    )
    np.testing.assert_allclose(via_cfg, via_scales, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Kernel tier: the MIGRATION CONTRACT — emitter vs verbatim legacy bodies
# ---------------------------------------------------------------------------
#
# The functions below are VERBATIM copies of the retired hand-written
# kernels (frozen at their pre-emitter text, PR 5 state). The tests drive
# them through the very same host entries by monkeypatching the kernel
# factories, so specs/scratch/layout are identical and any output
# difference is the emitter's fault. Do not "fix" or modernize these
# bodies — they ARE the contract.


def _legacy_group_gemm_kernel(
    e_ref, a_ref, b_ref, o_ref, acc_ref, *, n_k, out_dtype, act_fn=None,
):
    del e_ref
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    a = a_ref[:]
    if act_fn is not None:
        a = act_fn(a.astype(jnp.float32)).astype(a_ref.dtype)
    acc_ref[:] += jnp.dot(
        a, b_ref[0], preferred_element_type=jnp.float32
    )

    @pl.when(kk == n_k - 1)
    def _():
        o_ref[:] = acc_ref[:].astype(out_dtype)


def _legacy_group_gemm_w8_kernel(
    e_ref, a_ref, b_ref, s_ref, o_ref, acc_ref, *, n_k, out_dtype,
    act_fn=None,
):
    del e_ref
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    a = a_ref[:]
    if act_fn is not None:
        a = act_fn(a.astype(jnp.float32)).astype(a_ref.dtype)
    acc_ref[:] += jnp.dot(
        a, b_ref[0].astype(a_ref.dtype),
        preferred_element_type=jnp.float32,
    )

    @pl.when(kk == n_k - 1)
    def _():
        o_ref[:] = (acc_ref[:] * s_ref[0]).astype(out_dtype)


def _legacy_group_gemm_ragged_kernel(
    e_ref, v_ref, a_ref, b_ref, o_ref, acc_ref, *, n_k, out_dtype,
    act_fn=None, panel,
):
    del e_ref
    i = pl.program_id(1)
    kk = pl.program_id(2)
    valid = v_ref[i]

    @pl.when(kk == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    bm = acc_ref.shape[0]
    for p in range(bm // panel):
        @pl.when(p * panel < valid)
        def _(p=p):
            a = a_ref[pl.ds(p * panel, panel), :]
            if act_fn is not None:
                a = act_fn(a.astype(jnp.float32)).astype(a_ref.dtype)
            acc_ref[pl.ds(p * panel, panel), :] += jnp.dot(
                a, b_ref[0], preferred_element_type=jnp.float32
            )

    @pl.when(kk == n_k - 1)
    def _():
        rows = jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0)
        o_ref[:] = jnp.where(rows < valid, acc_ref[:], 0.0).astype(out_dtype)


def _legacy_group_gemm_w8_ragged_kernel(
    e_ref, v_ref, a_ref, b_ref, s_ref, o_ref, acc_ref, *, n_k, out_dtype,
    act_fn=None, panel,
):
    del e_ref
    i = pl.program_id(1)
    kk = pl.program_id(2)
    valid = v_ref[i]

    @pl.when(kk == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    bm = acc_ref.shape[0]
    for p in range(bm // panel):
        @pl.when(p * panel < valid)
        def _(p=p):
            a = a_ref[pl.ds(p * panel, panel), :]
            if act_fn is not None:
                a = act_fn(a.astype(jnp.float32)).astype(a_ref.dtype)
            acc_ref[pl.ds(p * panel, panel), :] += jnp.dot(
                a, b_ref[0].astype(a_ref.dtype),
                preferred_element_type=jnp.float32,
            )

    @pl.when(kk == n_k - 1)
    def _():
        rows = jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0)
        o_ref[:] = jnp.where(
            rows < valid, acc_ref[:] * s_ref[0], 0.0
        ).astype(out_dtype)


def _legacy_group_gemm_dw_kernel(e_ref, a_ref, g_ref, o_ref, acc_ref):
    i = pl.program_id(2)
    first_of_run = jnp.logical_or(
        i == 0, e_ref[jnp.maximum(i - 1, 0)] != e_ref[i]
    )

    @pl.when(first_of_run)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jax.lax.dot_general(
        a_ref[:].astype(jnp.float32), g_ref[:].astype(jnp.float32),
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[0] = acc_ref[:]


def _legacy_group_gemm_dw_ragged_kernel(e_ref, v_ref, a_ref, g_ref, o_ref,
                                        acc_ref, *, panel):
    i = pl.program_id(2)
    valid = v_ref[i]
    first_of_run = jnp.logical_or(
        i == 0, e_ref[jnp.maximum(i - 1, 0)] != e_ref[i]
    )

    @pl.when(first_of_run)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    bm = a_ref.shape[0]
    for p in range(bm // panel):
        @pl.when(p * panel < valid)
        def _(p=p):
            a = a_ref[pl.ds(p * panel, panel), :].astype(jnp.float32)
            rows = (
                jax.lax.broadcasted_iota(jnp.int32, a.shape, 0) + p * panel
            )
            a = jnp.where(rows < valid, a, 0.0)
            acc_ref[:] += jax.lax.dot_general(
                a, g_ref[pl.ds(p * panel, panel), :].astype(jnp.float32),
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
    o_ref[0] = acc_ref[:]


def _legacy_make_group_gemm_kernel(*, n_k, out_dtype, act_fn=None, fmt=None,
                                   ragged=False, panel=0):
    """Factory with the emitter factory's signature, dispatching to the
    verbatim legacy twins — the monkeypatch target."""
    w8 = bool(fmt is not None and fmt.w8)
    kw = dict(n_k=n_k, out_dtype=out_dtype, act_fn=act_fn)
    if ragged:
        kw["panel"] = panel
        k = (_legacy_group_gemm_w8_ragged_kernel if w8
             else _legacy_group_gemm_ragged_kernel)
    else:
        k = _legacy_group_gemm_w8_kernel if w8 else _legacy_group_gemm_kernel
    return functools.partial(k, **kw)


def _legacy_make_group_gemm_dw_kernel(*, ragged=False, panel=0):
    if ragged:
        return functools.partial(
            _legacy_group_gemm_dw_ragged_kernel, panel=panel
        )
    return _legacy_group_gemm_dw_kernel


def _legacy_ag_group_gemm_overlap_kernel(
    eid_ref, a_ref, b_ref,
    out_ref, ag_ref,
    a_all, b_buf, out_stage,
    copy_sem, send_sems, recv_sems, gsems, bsem, outsem,
    *, axis, n, nb, n_jn, bn, bpg, bm, out_dtype, vid_ref=None, panel=0,
):
    from triton_dist_tpu.ops.gg_pipeline import _ragged_block_emit

    me = shmem.my_pe(axis)
    t_pad_loc = nb * bm
    it_counter = [0]

    local = pltpu.make_async_copy(
        a_ref, ag_ref.at[pl.ds(me * t_pad_loc, t_pad_loc)], copy_sem
    )
    local.start()
    local.wait()
    shmem.barrier_all(axis)
    right = jax.lax.rem(me + 1, n)

    pltpu.make_async_copy(
        b_ref.at[eid_ref[me, 0], :, pl.ds(0, bn)], b_buf.at[0], bsem.at[0]
    ).start()
    slot_carry = [jnp.int32(1)]

    descs = []
    for s in range(n):
        c = jax.lax.rem(me - s + 2 * n, n)
        if s > 0:
            descs[s - 1].wait_recv()
        sl = pl.ds(c * t_pad_loc, t_pad_loc)
        if s < n - 1:
            descs.append(
                shmem.putmem_nbi_block(
                    ag_ref.at[sl], ag_ref.at[sl], right, axis,
                    send_sems.at[s], recv_sems.at[s],
                )
            )

        n_groups = (nb + bpg - 1) // bpg

        def _group_desc(g, slot, c=c):
            base = g * bpg * bm
            cnt = min(bpg * bm, t_pad_loc - base)
            return pltpu.make_async_copy(
                ag_ref.at[pl.ds(c * t_pad_loc + base, cnt), :],
                a_all.at[slot, pl.ds(0, cnt), :],
                gsems.at[slot],
            )

        _group_desc(0, 0).start()
        for g in range(n_groups):
            gslot = g % 2
            if g + 1 < n_groups:
                _group_desc(g + 1, 1 - gslot).start()
            _group_desc(g, gslot).wait()
            nb_g = min(bpg, nb - g * bpg)

            if g + 1 < n_groups:
                e_next = eid_ref[c, (g + 1) * bpg]
            elif s + 1 < n:
                c_next = jax.lax.rem(me - (s + 1) + 2 * n, n)
                e_next = eid_ref[c_next, 0]
            else:
                e_next = None
            it_base = it_counter[0]

            def _iter(i, slot, g=g, gslot=gslot, nb_g=nb_g, it_base=it_base,
                      e_next=e_next):
                jn = i // nb_g
                b_rel = jax.lax.rem(i, nb_g)
                b = g * bpg + b_rel
                e = eid_ref[c, b]
                prev_rel = jax.lax.rem(jax.lax.max(i - 1, 0), nb_g)
                fresh = jnp.logical_or(
                    i == 0,
                    jnp.logical_or(
                        jn != jax.lax.max(i - 1, 0) // nb_g,
                        e != eid_ref[c, g * bpg + prev_rel],
                    ),
                )
                slot = jnp.where(fresh, 1 - slot, slot)

                @pl.when(fresh)
                def _():
                    pltpu.make_async_copy(
                        b_ref.at[e, :, pl.ds(jn * bn, bn)],
                        b_buf.at[slot],
                        bsem.at[slot],
                    ).wait()

                nxt = i + 1
                jn2 = nxt // nb_g
                b2 = jax.lax.rem(nxt, nb_g)
                e2 = eid_ref[c, g * bpg + jax.lax.min(b2, nb_g - 1)]
                fresh2 = jnp.logical_and(
                    nxt < nb_g * n_jn,
                    jnp.logical_or(jn2 != jn, e2 != e),
                )
                jn2v = jn2
                if e_next is not None:
                    boundary = nxt >= nb_g * n_jn
                    e2 = jnp.where(boundary, e_next, e2)
                    jn2v = jnp.where(boundary, 0, jn2)
                    fresh2 = jnp.logical_or(fresh2, boundary)

                @pl.when(fresh2)
                def _():
                    pltpu.make_async_copy(
                        b_ref.at[e2, :, pl.ds(jn2v * bn, bn)],
                        b_buf.at[1 - slot],
                        bsem.at[1 - slot],
                    ).start()

                if vid_ref is None:
                    y = jnp.dot(
                        a_all[gslot, pl.ds(b_rel * bm, bm), :],
                        b_buf[slot],
                        preferred_element_type=jnp.float32,
                    )
                gi = it_base + i
                oslot = jax.lax.rem(gi, 2)

                @pl.when(gi >= 2)
                def _():
                    pltpu.make_async_copy(
                        out_stage.at[pl.ds(oslot * bm, bm), :],
                        out_ref.at[
                            pl.ds(c * t_pad_loc + b * bm, bm),
                            pl.ds(jn * bn, bn),
                        ],
                        outsem.at[oslot],
                    ).wait()

                if vid_ref is None:
                    out_stage[pl.ds(oslot * bm, bm), :] = y.astype(out_dtype)
                else:
                    _ragged_block_emit(
                        lambda off, rows: a_all[
                            gslot, pl.ds(b_rel * bm + off, rows), :
                        ],
                        b_buf[slot], out_stage, oslot * bm, vid_ref[c, b],
                        bm, bn, panel, out_dtype,
                    )
                pltpu.make_async_copy(
                    out_stage.at[pl.ds(oslot * bm, bm), :],
                    out_ref.at[
                        pl.ds(c * t_pad_loc + b * bm, bm), pl.ds(jn * bn, bn)
                    ],
                    outsem.at[oslot],
                ).start()
                return slot

            slot_carry[0] = jax.lax.fori_loop(
                0, nb_g * n_jn, _iter, slot_carry[0]
            )
            it_counter[0] += nb_g * n_jn
    total_iters = n * nb * n_jn

    def _drain(oslot):
        pltpu.make_async_copy(
            out_stage.at[pl.ds(oslot * bm, bm), :],
            out_ref.at[pl.ds(0, bm), pl.ds(0, bn)],
            outsem.at[oslot],
        ).wait()

    if total_iters >= 1:
        _drain((total_iters - 1) % 2)
    if total_iters >= 2:
        _drain(total_iters % 2)
    shmem.quiet(*descs)


def _legacy_make_ag_overlap_kernel(*, axis, n, nb, n_jn, bn, bpg, bm,
                                   out_dtype, spans, ragged=False, panel=0,
                                   fmt=None):
    assert len(spans) == 1, "legacy reference covers the chunk=1 contract"
    assert fmt is None or not fmt.w8, "legacy reference is bf16-only"
    kw = dict(axis=axis, n=n, nb=nb, n_jn=n_jn, bn=bn, bpg=bpg, bm=bm,
              out_dtype=out_dtype, panel=panel)
    if ragged:
        def kernel(eid_ref, vid_ref, *rest):
            _legacy_ag_group_gemm_overlap_kernel(
                eid_ref, *rest, vid_ref=vid_ref, **kw
            )
        return kernel
    return functools.partial(_legacy_ag_group_gemm_overlap_kernel, **kw)


def _legacy_moe_reduce_rs_overlap_kernel(
    eid_ref, h_ref, w_ref, dst_ref, wrow_ref,
    out_ref, own_buf, landing,
    h_buf, w_buf, push_stage, ids_v, w_v, partial_ref,
    hsem, wsem, metasem, stage_sem, recv_sems,
    *, axis, n, nb, n_jn, bn, m_out, out_dtype, vid_ref=None, panel=0,
):
    from triton_dist_tpu.ops.gg_pipeline import _moe_ragged_blk
    from triton_dist_tpu.utils import pick_block

    me = shmem.my_pe(axis)
    t_pad_tot, f_loc = h_ref.shape
    t_pad_loc = t_pad_tot // n
    bm = t_pad_loc // nb
    cdt = h_ref.dtype
    if n > 1:
        shmem.barrier_all(axis)

    def _issue_h(c, b, slot):
        pltpu.make_async_copy(
            h_ref.at[pl.ds(c * t_pad_loc + b * bm, bm), :],
            h_buf.at[slot],
            hsem.at[slot],
        ).start()

    for s in range(n):
        c = jax.lax.rem(me + 1 + s, n) if n > 1 else jnp.int32(0)
        ids_cp = pltpu.make_async_copy(dst_ref.at[c], ids_v, metasem)
        ids_cp.start()
        w_cp = pltpu.make_async_copy(wrow_ref.at[c], w_v, metasem)
        w_cp.start()
        ids_cp.wait()
        w_cp.wait()

        for jn in range(n_jn):
            partial_ref[:] = jnp.zeros_like(partial_ref)
            e0 = eid_ref[c, 0]
            pltpu.make_async_copy(
                w_ref.at[e0, :, pl.ds(jn * bn, bn)], w_buf.at[0], wsem.at[0]
            ).start()
            _issue_h(c, 0, 0)

            def _blk(b, slot):
                e = eid_ref[c, b]
                e_prev = eid_ref[c, jax.lax.max(b - 1, 0)]
                fresh = jnp.logical_or(b == 0, e != e_prev)
                slot = jnp.where(fresh, 1 - slot, slot)

                @pl.when(fresh)
                def _():
                    pltpu.make_async_copy(
                        w_ref.at[e, :, pl.ds(jn * bn, bn)],
                        w_buf.at[slot],
                        wsem.at[slot],
                    ).wait()

                e2 = eid_ref[c, jax.lax.min(b + 1, nb - 1)]

                @pl.when(jnp.logical_and(b + 1 < nb, e2 != e))
                def _():
                    pltpu.make_async_copy(
                        w_ref.at[e2, :, pl.ds(jn * bn, bn)],
                        w_buf.at[1 - slot],
                        wsem.at[1 - slot],
                    ).start()

                hslot = jax.lax.rem(b, 2)
                pltpu.make_async_copy(
                    h_ref.at[pl.ds(0, bm), :], h_buf.at[hslot], hsem.at[hslot]
                ).wait()

                @pl.when(b + 1 < nb)
                def _():
                    pltpu.make_async_copy(
                        h_ref.at[
                            pl.ds(c * t_pad_loc + (b + 1) * bm, bm), :
                        ],
                        h_buf.at[1 - hslot],
                        hsem.at[1 - hslot],
                    ).start()

                if vid_ref is None:
                    y = jnp.dot(
                        h_buf[hslot],
                        w_buf[slot],
                        preferred_element_type=jnp.float32,
                    )
                    d = ids_v[b]
                    w_r = w_v[b]
                    sel = jax.lax.broadcasted_iota(
                        jnp.int32, (m_out, bm), 0
                    ) == d[None, :]
                    scat = jnp.where(sel, w_r[None, :], 0.0).astype(cdt)
                    partial_ref[:] += jnp.dot(
                        scat, y.astype(cdt), preferred_element_type=jnp.float32
                    )
                else:
                    _moe_ragged_blk(
                        h_buf, w_buf, ids_v, w_v, partial_ref, hslot, slot,
                        b, vid_ref[c, b], m_out, bm, panel, cdt,
                    )
                return slot

            jax.lax.fori_loop(0, nb, _blk, jnp.int32(1))

            pc = s * n_jn + jn
            pslot = pc % 2

            def _stage_wait(sl):
                pltpu.make_async_copy(
                    push_stage.at[sl], own_buf.at[:, pl.ds(0, bn)],
                    stage_sem.at[sl],
                ).wait()

            if pc >= 2:
                _stage_wait(pslot)
            push_stage[pslot] = partial_ref[:].astype(out_dtype)
            if s < n - 1:
                shmem.putmem_nbi_block(
                    landing.at[s, :, pl.ds(jn * bn, bn)],
                    push_stage.at[pslot],
                    c, axis, stage_sem.at[pslot], recv_sems.at[s, jn],
                )
            else:
                pltpu.make_async_copy(
                    push_stage.at[pslot],
                    (out_ref if n == 1 else own_buf).at[:, pl.ds(jn * bn, bn)],
                    stage_sem.at[pslot],
                ).start()

    total_push = n * n_jn
    if total_push >= 1:
        pltpu.make_async_copy(
            push_stage.at[(total_push - 1) % 2], own_buf.at[:, pl.ds(0, bn)],
            stage_sem.at[(total_push - 1) % 2],
        ).wait()
    if total_push >= 2:
        pltpu.make_async_copy(
            push_stage.at[total_push % 2], own_buf.at[:, pl.ds(0, bn)],
            stage_sem.at[total_push % 2],
        ).wait()
    if n == 1:
        return

    for d in range(n - 1):
        for jn in range(n_jn):
            pltpu.make_async_copy(
                landing.at[d, :, pl.ds(jn * bn, bn)],
                own_buf.at[:, pl.ds(jn * bn, bn)],
                recv_sems.at[d, jn],
            ).wait()

    h_dim = out_ref.shape[1]
    bmo = pick_block(m_out, 256)
    bno = pick_block(h_dim, 1024)

    def reduce_body(*blks):
        o_blk = blks[-1]
        acc = blks[0][:].astype(jnp.float32)
        for r in blks[1:-1]:
            acc = acc + r[:].astype(jnp.float32)
        o_blk[:] = acc.astype(out_dtype)

    blk = lambda i, j: (i, j)  # noqa: E731
    pltpu.emit_pipeline(
        reduce_body,
        grid=(m_out // bmo, h_dim // bno),
        in_specs=[pl.BlockSpec((bmo, bno), blk)] * n,
        out_specs=[pl.BlockSpec((bmo, bno), blk)],
    )(
        own_buf,
        *(landing.at[d] for d in range(n - 1)),
        out_ref,
    )


def _legacy_make_moe_rs_overlap_kernel(*, axis, n, nb, n_jn, bn, m_out,
                                       out_dtype, spans, ragged=False,
                                       panel=0, fmt=None):
    assert len(spans) == 1, "legacy reference covers the chunk=1 contract"
    assert fmt is None or not fmt.w8, "legacy reference is bf16-only"
    kw = dict(axis=axis, n=n, nb=nb, n_jn=n_jn, bn=bn, m_out=m_out,
              out_dtype=out_dtype, panel=panel)
    if ragged:
        def kernel(eid_ref, vid_ref, *rest):
            _legacy_moe_reduce_rs_overlap_kernel(
                eid_ref, *rest, vid_ref=vid_ref, **kw
            )
        return kernel
    return functools.partial(_legacy_moe_reduce_rs_overlap_kernel, **kw)


@pytest.fixture
def _small_panels(monkeypatch):
    """Shrink the MXU row panel so interpreter-scale blocks (bm=8) still
    exercise multi-panel skipping (2 panels per block)."""
    monkeypatch.setattr(gg_mod, "_PANEL_ROWS", 4)


@pytest.mark.parametrize("variant", ["fwd", "w8", "ragged", "w8_ragged"])
def test_emitter_grid_bit_exact_vs_legacy(monkeypatch, _small_panels, variant):
    """The migration contract, grid family: the emitter's generated kernel
    is BIT-EXACT to the verbatim legacy twin at every policy tuple —
    including the fused act_fn epilogue."""
    ids = _case_ids()
    E, bm = 4, 8
    al = moe_align_block_size(ids, E, bm, ragged=True)
    t_pad = al.sorted_token_ids.shape[0]
    a = jax.random.normal(jax.random.PRNGKey(5), (t_pad, 32), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(6), (E, 32, 64), jnp.float32)
    b_q, sc = quantize_expert_weights(b)
    ragged = "ragged" in variant
    w8 = variant.startswith("w8")
    cfg = GroupGemmConfig(bm, 64, 32, ragged=ragged)

    def run():
        if w8:
            return np.asarray(group_gemm_w8(
                a, b_q, sc, al.expert_ids,
                valid_rows=al.valid_rows if ragged else None, config=cfg,
                act_fn=jax.nn.silu,
            ))
        return np.asarray(group_gemm(
            a, b, al.expert_ids,
            valid_rows=al.valid_rows if ragged else None, config=cfg,
            act_fn=jax.nn.silu,
        ))

    emitted = run()
    monkeypatch.setattr(
        gg_mod, "make_group_gemm_kernel", _legacy_make_group_gemm_kernel
    )
    legacy = run()
    np.testing.assert_array_equal(emitted, legacy)


@pytest.mark.parametrize("ragged", [False, True])
def test_emitter_dw_bit_exact_vs_legacy(monkeypatch, _small_panels, ragged):
    """Migration contract, dW family."""
    ids = _case_ids()
    E, bm = 4, 8
    al = moe_align_block_size(ids, E, bm, ragged=True)
    t_pad = al.sorted_token_ids.shape[0]
    a = jax.random.normal(jax.random.PRNGKey(7), (t_pad, 32), jnp.float32)
    g = jax.random.normal(jax.random.PRNGKey(8), (t_pad, 64), jnp.float32)
    cfg = GroupGemmConfig(bm, 64, 32, ragged=ragged)

    def run():
        return np.asarray(group_gemm_dw(
            a, g, al.expert_ids, E,
            valid_rows=al.valid_rows if ragged else None, config=cfg,
            assume_sorted=True,
        ))

    emitted = run()
    monkeypatch.setattr(
        gg_mod, "make_group_gemm_dw_kernel", _legacy_make_group_gemm_dw_kernel
    )
    legacy = run()
    np.testing.assert_array_equal(emitted, legacy)


def _overlap_pipeline(mesh, cfg, m_loc=8, topk=2, n_exp=3, h_dim=32,
                      f_dim=64, seed=21):
    """Drive BOTH overlap families through tp_moe_mlp_grad on a 4-PE mesh
    (the fused up-projection feeds the fused down-projection)."""
    from triton_dist_tpu.ops.grads import tp_moe_mlp_grad

    n = len(mesh.devices.flat)
    m_tot = n * m_loc
    kx, ku, kd, kl = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(kx, (m_tot, h_dim), jnp.float32)
    w_up = jax.random.normal(ku, (n_exp, h_dim, f_dim)) / 8
    w_down = jax.random.normal(kd, (n_exp, f_dim, h_dim)) / 8
    tw, ids = select_experts(
        jax.random.normal(kl, (m_tot, n_exp), jnp.float32), topk
    )
    specs = (
        P("tp", None), P(None, None, "tp"), P(None, "tp", None),
        P("tp", None), P("tp", None),
    )
    return np.asarray(jax.jit(
        jax.shard_map(
            lambda x, wu, wd, i, t: tp_moe_mlp_grad(
                x, wu, wd, i, t, "tp", jax.nn.gelu, cfg, None, True
            ),
            mesh=mesh, in_specs=specs, out_specs=P("tp", None),
            check_vma=False,
        )
    )(x, w_up, w_down, ids, tw.astype(jnp.float32)), np.float32)


@pytest.mark.parametrize("ragged", [False, True])
def test_emitter_overlap_bit_exact_vs_legacy(
    monkeypatch, mesh4, _small_panels, ragged,
):
    """Migration contract, both overlap families at once: the fused
    pipeline (chunk=1, bf16, padded/ragged) with the emitter's kernels is
    BIT-EXACT to the same pipeline with the verbatim legacy bodies."""
    cfg = GroupGemmConfig(4, 32, 32, ragged=ragged)
    emitted = _overlap_pipeline(mesh4, cfg)
    monkeypatch.setattr(
        agg_mod, "make_ag_overlap_kernel", _legacy_make_ag_overlap_kernel
    )
    monkeypatch.setattr(
        rs_mod, "make_moe_rs_overlap_kernel", _legacy_make_moe_rs_overlap_kernel
    )
    legacy = _overlap_pipeline(mesh4, cfg)
    np.testing.assert_array_equal(emitted, legacy)


@pytest.mark.parametrize("chunks,ragged", [(1, False), (1, True), (2, False),
                                           (2, True)])
def test_w8_overlap_kernels_match_sequential(mesh4, _small_panels, chunks,
                                             ragged):
    """w8 through the REAL fused kernels (every schedule × validity
    combination) vs the sequential w8 composition on the same quantized
    banks — the payoff axis, kernel tier."""
    from triton_dist_tpu.ops.grads import tp_moe_mlp_grad

    n, m_loc, topk, n_exp, h_dim, f_dim = 4, 8, 2, 3, 32, 64
    m_tot = n * m_loc
    kx, ku, kd, kl = jax.random.split(jax.random.PRNGKey(91), 4)
    x = jax.random.normal(kx, (m_tot, h_dim), jnp.float32)
    w_up = jax.random.normal(ku, (n_exp, h_dim, f_dim)) / 8
    w_down = jax.random.normal(kd, (n_exp, f_dim, h_dim)) / 8
    tw, ids = select_experts(
        jax.random.normal(kl, (m_tot, n_exp), jnp.float32), topk
    )
    specs = (
        P("tp", None), P(None, None, "tp"), P(None, "tp", None),
        P("tp", None), P("tp", None),
    )

    def run(overlap, cfg):
        return np.asarray(jax.jit(
            jax.shard_map(
                lambda x, wu, wd, i, t: tp_moe_mlp_grad(
                    x, wu, wd, i, t, "tp", jax.nn.gelu, cfg, None, overlap
                ),
                mesh=mesh4, in_specs=specs, out_specs=P("tp", None),
                check_vma=False,
            )
        )(x, w_up, w_down, ids, tw.astype(jnp.float32)), np.float32)

    fused = run(True, GroupGemmConfig(
        4, 32, 32, chunks_per_shard=chunks, ragged=ragged, w8=True,
    ))
    seq = run(False, GroupGemmConfig(4, 32, 32, w8=True))
    np.testing.assert_allclose(fused, seq, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Chaos: the w8 ragged chunked pipeline adds no droppable signal edge
# ---------------------------------------------------------------------------

TIMEOUT_ITERS = 300


@pytest.fixture
def _chaos_config():
    snap = (
        tdt_config.get_config().timeout_iters,
        tdt_config.get_config().fault_plan,
        tdt_config.get_config().raise_on_timeout,
    )
    yield
    tdt_config.update(
        timeout_iters=snap[0], fault_plan=snap[1], raise_on_timeout=snap[2]
    )


def _chaos_pipeline(cfg):
    """The w8 ragged chunked pipeline at combine-chunk-engaging scale on a
    2-PE mesh; the golden is the SEQUENTIAL w8 composition (same quantized
    banks, so the comparison is tight)."""
    from triton_dist_tpu.ops.grads import tp_moe_mlp_op

    mesh2 = Mesh(np.array(jax.devices()[:2]), ("tp",))
    n_exp, topk, m_tot, h_dim, f_dim = 2, 1, 512, 16, 32
    kx, ku, kd, kl = jax.random.split(jax.random.PRNGKey(61), 4)
    x = jax.random.normal(kx, (m_tot, h_dim), jnp.float32)
    w_up = jax.random.normal(ku, (n_exp, h_dim, f_dim)) / 8
    w_down = jax.random.normal(kd, (n_exp, f_dim, h_dim)) / 8
    tw, ids = select_experts(
        jax.random.normal(kl, (m_tot, n_exp), jnp.float32), topk
    )
    golden = tp_moe_mlp_op(
        x, w_up, w_down, ids, tw, mesh2,
        config=GroupGemmConfig(4, 32, 16, w8=True), overlap=False,
    )
    out = tp_moe_mlp_op(
        x, w_up, w_down, ids, tw, mesh2, config=cfg, overlap=True
    )
    return np.asarray(golden, np.float32), np.asarray(out, np.float32)


@pytest.mark.chaos
@pytest.mark.parametrize("site", [1, 2])
def test_w8_chunk_signal_drop_no_new_edge(_chaos_config, site):
    """Dropping a chunk signal under the w8 RAGGED CHUNKED pipeline
    behaves exactly like the bf16 schedules: either the watchdog trips
    with a diagnostic naming only PRE-EXISTING kinds (the w8 scale DMAs
    are local data-coupled copies — no new droppable edge) or the run
    completes exact. Never silent corruption."""
    tdt_config.update(
        timeout_iters=TIMEOUT_ITERS,
        fault_plan=FaultPlan("drop_signal", pe=-1, site=site),
        raise_on_timeout=True,
    )
    cfg = GroupGemmConfig(4, 32, 16, chunks_per_shard=2, ragged=True, w8=True)
    try:
        golden, out = _chaos_pipeline(cfg)
    except R.DistTimeoutError as e:
        assert e.records, "timeout must carry decoded records"
        kinds = {r["kind"] for r in e.records}
        assert kinds <= {
            "chunk_wait", "barrier_all", "wait", "signal_wait_until"
        }, kinds
        return
    np.testing.assert_allclose(out, golden, rtol=1e-5, atol=1e-5)


@pytest.mark.chaos
def test_w8_chunk_signal_dup_never_corrupts(_chaos_config):
    """A duplicated chunk signal under the w8 ragged chunked pipeline must
    end exact or loud — never silently wrong."""
    import re

    tdt_config.update(
        timeout_iters=TIMEOUT_ITERS,
        fault_plan=FaultPlan("dup_signal", pe=-1, site=1),
        raise_on_timeout=True,
    )
    cfg = GroupGemmConfig(4, 32, 16, chunks_per_shard=2, ragged=True, w8=True)
    try:
        golden, out = _chaos_pipeline(cfg)
    except R.DistTimeoutError as e:
        assert e.records
        return
    except Exception as e:  # noqa: BLE001 — classified, as in test_chaos
        assert re.search(r"semaphore|barrier|race", str(e), re.IGNORECASE), e
        return
    np.testing.assert_allclose(out, golden, rtol=1e-5, atol=1e-5)
