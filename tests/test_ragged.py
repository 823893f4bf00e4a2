"""Ragged grouped GEMM — killing the MoE padding tax (ISSUE 5).

Three tiers, matching the repo's environment matrix (tests/test_chunked*):

- **host-level** (runs everywhere): the ragged alignment's per-block
  ``(expert_id, valid_rows)`` map invariants, the padding-tax perf-model
  terms and the ``suggest_ragged`` pruning hook, the tune-space ordering
  contract (every ragged candidate strictly after its padded twin,
  composed with the PR 3/4 chunk invariant) and the slowest-rank
  autotune aggregation.
- **kernel-level** (needs the Mosaic TPU interpreter — this jax line
  cannot build or simulate the fused kernels, the pre-existing seed gap):
  ragged vs the ``jax.lax.ragged_dot`` golden at non-divisor expert
  counts (zero-row expert, single-row tail), ``ragged=False`` ≡ legacy
  bit-exact for forward / w8 / dw and both overlapped pipeline kernels,
  the dw in-kernel row masking; the whole-pipeline cells (ragged vs
  padded, ragged × chunks_per_shard, the sentinel, EP) are
  test_ragged_pipeline.py.
- **chaos**: ragged tail blocks must not add a droppable signal edge — a
  dropped/duplicated chunk signal under the ragged chunked pipeline
  either trips the watchdogged ``chunk_wait`` diagnostic or leaves the
  result exact, exactly like the padded schedule; never corruption.
"""

import importlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from triton_dist_tpu import config as tdt_config
from triton_dist_tpu import perf_model as pm
# ops/__init__ re-exports functions that shadow these submodule names,
# and `import a.b.c as x` binds through the attribute chain
gg_mod = importlib.import_module("triton_dist_tpu.ops.group_gemm")
from triton_dist_tpu.ops.group_gemm import GroupGemmConfig, group_gemm
from triton_dist_tpu.ops.moe_utils import (
    moe_align_block_size,
    moe_align_ranked,
    ranked_global_view,
    select_experts,
    valid_rows_from_sorted,
)
from triton_dist_tpu.resilience import FaultPlan
from triton_dist_tpu.resilience import records as R


def _case_ids():
    """Non-divisor routing: expert counts [5, 0, 12, 1] — a tail of 5, a
    ZERO-row expert, a 12 (one full block + tail 4 at bm=8), and a
    single-row tail."""
    return jnp.concatenate(
        [
            jnp.zeros(5, jnp.int32),
            jnp.full(12, 2, jnp.int32),
            jnp.full(1, 3, jnp.int32),
        ]
    )


# ---------------------------------------------------------------------------
# Host tier
# ---------------------------------------------------------------------------

def test_moe_align_ragged_valid_rows():
    ids = _case_ids()
    E, bm = 4, 8
    t = ids.shape[0]
    al = jax.jit(
        lambda i: moe_align_block_size(i, E, bm, ragged=True)
    )(ids)
    vr = np.asarray(al.valid_rows)
    sti = np.asarray(al.sorted_token_ids)
    # the map IS the per-block live count (valid rows are a block prefix)
    np.testing.assert_array_equal(vr, (sti.reshape(-1, bm) < t).sum(1))
    assert vr.sum() == t
    # single-row tail and zero trailing blocks both appear
    assert 1 in vr and 0 in vr
    # the reconstruction helper agrees with the builder
    np.testing.assert_array_equal(
        np.asarray(valid_rows_from_sorted(al.sorted_token_ids, bm, t)), vr
    )
    # legacy mode stays map-free
    assert moe_align_block_size(ids, E, bm).valid_rows is None
    # ranked + global view carry the map through
    ral = moe_align_ranked(
        jnp.tile(ids[:16], (2, 1)), E, bm, 8, ragged=True
    )
    assert ral.valid_rows.shape == ral.expert_ids.shape
    gv = ranked_global_view(ral, 8, 2)
    np.testing.assert_array_equal(
        np.asarray(gv.valid_rows), np.asarray(ral.valid_rows).reshape(-1)
    )
    assert moe_align_ranked(
        jnp.tile(ids[:16], (2, 1)), E, bm, 8
    ).valid_rows is None


def test_pad_tax_model_and_suggest():
    # bench shape: 16384 real rows at block_m=512 — the padded grid
    # computes the static worst case 20480, ragged ~16894 → tax ≈ 0.175,
    # predicted recovery ≈ 1.21x (the ~25% tax relative to real rows)
    tax = pm.estimate_group_gemm_pad_tax(16384, 8, 512)
    assert 0.15 < tax < 0.20
    assert 1.15 < 1.0 / (1.0 - tax) < 1.25
    assert pm.suggest_ragged(16384, 8, 512)
    # block_m at/below the panel over a huge problem: the worst-case slack
    # is a rounding error — ragged can't help, the hook prunes it
    assert not pm.suggest_ragged(10_000_000, 8, 128)
    # exact-counts form: counts divisible by the PANEL leave only the
    # static worst-case slack — negligible once t dwarfs E·block_m, so
    # the suggester prunes ragged there ("divisible shapes")
    assert pm.estimate_group_gemm_pad_tax(
        16384, 2, 128, counts=[8192, 8192]
    ) < 0.02
    assert not pm.suggest_ragged(16384, 2, 128, counts=[8192, 8192])
    # bigger blocks always carry more tax at the same counts
    assert pm.estimate_group_gemm_pad_tax(
        1024, 8, 512, counts=[128] * 8
    ) > pm.estimate_group_gemm_pad_tax(1024, 8, 128, counts=[128] * 8)
    # degenerate inputs never blow up
    assert pm.estimate_group_gemm_pad_tax(0, 8, 512) == 0.0
    # the bench-shape accounting evidence (acceptance criterion): with
    # panel-divisible counts the ragged schedule computes ZERO pad rows —
    # the tax is exactly the 4096 static pad rows the padded grid burns
    # (20480 computed for 16384 real), all of them recovered
    assert pm.estimate_group_gemm_pad_tax(
        16384, 8, 512, counts=[2048] * 8
    ) == pytest.approx((20480 - 16384) / 20480)


def _ragged_like(cfg):
    return cfg.ragged or cfg.backend != "pallas"


def test_ragged_tune_space_ordering():
    """Every ragged candidate sits strictly AFTER its padded twin, in all
    three grouped-GEMM spaces, while the PR 3/4 chunk invariant (chunked
    strictly after every chunk=1) keeps holding — so no sweep-free walk
    can apply an untimed ragged OR chunked schedule."""
    from triton_dist_tpu.ops.allgather_group_gemm import (
        AG_GROUP_GEMM_TUNE_SPACE,
    )
    from triton_dist_tpu.ops.grads import TP_MOE_TUNE_SPACE
    from triton_dist_tpu.ops.moe_reduce_rs import MOE_RS_TUNE_SPACE

    for space in (
        TP_MOE_TUNE_SPACE, AG_GROUP_GEMM_TUNE_SPACE, MOE_RS_TUNE_SPACE,
    ):
        assert any(c.ragged for c in space), "space must sweep the axis"
        # the leader stays the proven padded config
        assert not _ragged_like(space[0])
        for i, c in enumerate(space):
            if c.ragged:
                twin = dataclasses.replace(c, ragged=False)
                assert twin in space[:i], (
                    f"ragged candidate {c} has no earlier padded twin"
                )
    # chunk invariant unchanged on the pipeline space
    chunked = [c.chunks_per_shard > 1 for c in TP_MOE_TUNE_SPACE]
    fi = chunked.index(True)
    assert all(chunked[fi:]) and not any(chunked[:fi])
    # the ragged_dot sentinel exists exactly once, after every padded
    # chunk=1 candidate (VERDICT r5 #1's in-tuner A/B)
    sent = [i for i, c in enumerate(TP_MOE_TUNE_SPACE)
            if c.backend == "ragged_dot"]
    assert len(sent) == 1
    for i, c in enumerate(TP_MOE_TUNE_SPACE):
        if not _ragged_like(c) and c.chunks_per_shard == 1:
            assert i < sent[0]


def test_moe_block_sensible_ragged_pruning():
    """The precondition hook prunes ragged candidates when the model says
    the tax is negligible, and can never remove a padded candidate."""
    from triton_dist_tpu.ops.grads import _moe_block_sensible

    def args_for(m, topk, E, h=32, f=64):
        x = jnp.zeros((m, h), jnp.bfloat16)
        wu = jnp.zeros((E, h, f), jnp.bfloat16)
        wd = jnp.zeros((E, f, h), jnp.bfloat16)
        ids = jnp.tile(jnp.arange(topk, dtype=jnp.int32), (m, 1)) % E
        tw = jnp.zeros((m, topk), jnp.float32)
        return (x, wu, wd, ids, tw)

    # bench-ish shape: big tax, ragged survives (padded trivially does)
    big = args_for(8192, 2, 8)
    assert _moe_block_sensible(GroupGemmConfig(512, 1024, 512), *big)
    assert _moe_block_sensible(
        GroupGemmConfig(512, 1024, 512, ragged=True), *big
    )
    # huge problem at panel-sized blocks: tax is a rounding error —
    # ragged (and the sentinel) are pruned, the padded twin survives
    tiny_tax = args_for(65536, 2, 4)
    assert _moe_block_sensible(GroupGemmConfig(128, 1024, 512), *tiny_tax)
    assert not _moe_block_sensible(
        GroupGemmConfig(128, 1024, 512, ragged=True), *tiny_tax
    )
    assert not _moe_block_sensible(
        GroupGemmConfig(128, 1024, 512, backend="ragged_dot"), *tiny_tax
    )


def test_slowest_rank_best():
    """Min-max cross-rank aggregation (VERDICT r5 missing #3): the config
    fastest for the SLOWEST rank wins — not rank 0's local argmin."""
    from triton_dist_tpu.autotuner import _slowest_rank_best

    # rank 0 would pick config 0 (1ms local); rank 1's 10ms makes its
    # worst case lose to config 1's 6ms
    assert _slowest_rank_best([[1.0, 5.0], [10.0, 6.0]]) == 1
    # a config that failed anywhere (inf) is disqualified everywhere
    assert _slowest_rank_best([[1.0, float("inf")], [10.0, 2.0]]) == 0
    assert _slowest_rank_best(
        [[float("inf"), 2.0], [1.0, 2.0]]
    ) == 1
    # every config failed somewhere: caller keeps its local pick
    assert _slowest_rank_best([[float("inf")], [1.0]]) == -1
    # order preference: a later candidate must win by the margin
    assert _slowest_rank_best([[1.0, 0.99], [1.0, 0.99]]) == 0
    assert _slowest_rank_best([[1.0, 0.90], [1.0, 0.90]]) == 1


def test_group_gemm_ragged_requires_valid_rows():
    a = jnp.zeros((16, 32), jnp.float32)
    b = jnp.zeros((2, 32, 64), jnp.float32)
    eids = jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError, match="valid_rows"):
        group_gemm(
            a, b, eids, config=GroupGemmConfig(8, 64, 32, ragged=True)
        )
    from triton_dist_tpu.ops.group_gemm import group_gemm_dw

    with pytest.raises(ValueError, match="valid_rows"):
        group_gemm_dw(
            a, a, eids, 2, config=GroupGemmConfig(8, 32, 32, ragged=True)
        )


# ---------------------------------------------------------------------------
# Kernel tier (Mosaic TPU interpreter required)
# ---------------------------------------------------------------------------

@pytest.fixture
def _small_panels(monkeypatch):
    """Shrink the MXU row panel so interpreter-scale blocks (bm=8) still
    exercise multi-panel skipping (2 panels per block)."""
    monkeypatch.setattr(gg_mod, "_PANEL_ROWS", 4)


def test_group_gemm_ragged_vs_ragged_dot(_small_panels):
    """Ragged kernel vs the jax.lax.ragged_dot golden over the PACKED live
    rows, at non-divisor counts (zero-row expert, single-row tail); dead
    rows come back exact zeros."""
    ids = _case_ids()
    E, bm = 4, 8
    t = ids.shape[0]
    al = moe_align_block_size(ids, E, bm, ragged=True)
    t_pad = al.sorted_token_ids.shape[0]
    a = jax.random.normal(jax.random.PRNGKey(0), (t_pad, 32), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (E, 32, 64), jnp.float32)
    out = group_gemm(
        a, b, al.expert_ids, valid_rows=al.valid_rows,
        config=GroupGemmConfig(bm, 64, 32, ragged=True),
    )
    live = np.asarray(al.sorted_token_ids) < t
    packed = jnp.asarray(np.asarray(a)[live])
    counts = jnp.bincount(ids, length=E)
    want = jax.lax.ragged_dot(packed, b, group_sizes=counts)
    np.testing.assert_allclose(
        np.asarray(out)[live], np.asarray(want), rtol=1e-4, atol=1e-4
    )
    assert np.all(np.asarray(out)[~live] == 0)


def test_group_gemm_ragged_false_bit_exact(_small_panels):
    """ragged=False dispatches to the byte-identical legacy kernels:
    forward, w8 and dw agree BIT-EXACTLY with the default config, with or
    without a valid_rows argument in hand."""
    from triton_dist_tpu.ops.group_gemm import (
        group_gemm_dw, group_gemm_w8, quantize_expert_weights,
    )

    ids = _case_ids()
    E, bm = 4, 8
    al = moe_align_block_size(ids, E, bm, ragged=True)
    t_pad = al.sorted_token_ids.shape[0]
    a = jax.random.normal(jax.random.PRNGKey(2), (t_pad, 32), jnp.float32)
    g = jax.random.normal(jax.random.PRNGKey(3), (t_pad, 64), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(4), (E, 32, 64), jnp.float32)
    off = GroupGemmConfig(bm, 64, 32, ragged=False)
    base = GroupGemmConfig(bm, 64, 32)
    np.testing.assert_array_equal(
        np.asarray(group_gemm(
            a, b, al.expert_ids, valid_rows=al.valid_rows, config=off
        )),
        np.asarray(group_gemm(a, b, al.expert_ids, config=base)),
    )
    b_q, sc = quantize_expert_weights(b)
    np.testing.assert_array_equal(
        np.asarray(group_gemm_w8(
            a, b_q, sc, al.expert_ids, valid_rows=al.valid_rows, config=off
        )),
        np.asarray(group_gemm_w8(a, b_q, sc, al.expert_ids, config=base)),
    )
    np.testing.assert_array_equal(
        np.asarray(group_gemm_dw(
            a, g, al.expert_ids, E, valid_rows=al.valid_rows, config=off,
            assume_sorted=True,
        )),
        np.asarray(group_gemm_dw(
            a, g, al.expert_ids, E, config=base, assume_sorted=True
        )),
    )


def test_group_gemm_ragged_live_rows_bit_exact(_small_panels):
    """Ragged changes WHICH rows are computed, never their math: per-row
    K-reduction order is untouched, so live rows match the padded kernel
    bit for bit (and the w8 scale fold is unchanged)."""
    from triton_dist_tpu.ops.group_gemm import (
        group_gemm_w8, quantize_expert_weights,
    )

    ids = _case_ids()
    E, bm = 4, 8
    t = ids.shape[0]
    al = moe_align_block_size(ids, E, bm, ragged=True)
    t_pad = al.sorted_token_ids.shape[0]
    a = jax.random.normal(jax.random.PRNGKey(5), (t_pad, 32), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(6), (E, 32, 64), jnp.float32)
    live = np.asarray(al.sorted_token_ids) < t
    ragged = GroupGemmConfig(bm, 64, 32, ragged=True)
    padded = GroupGemmConfig(bm, 64, 32)
    got = np.asarray(group_gemm(
        a, b, al.expert_ids, valid_rows=al.valid_rows, config=ragged
    ))
    ref = np.asarray(group_gemm(a, b, al.expert_ids, config=padded))
    np.testing.assert_array_equal(got[live], ref[live])
    b_q, sc = quantize_expert_weights(b)
    got8 = np.asarray(group_gemm_w8(
        a, b_q, sc, al.expert_ids, valid_rows=al.valid_rows, config=ragged
    ))
    ref8 = np.asarray(group_gemm_w8(a, b_q, sc, al.expert_ids, config=padded))
    np.testing.assert_array_equal(got8[live], ref8[live])


def test_group_gemm_dw_ragged_masks_junk(_small_panels):
    """dw zeroes masked rows BEFORE AᵀG: poison every pad row with huge
    junk — the ragged dW must still match the live-rows golden exactly
    (the padded kernel relies on the caller pre-zeroing instead)."""
    from triton_dist_tpu.ops.group_gemm import group_gemm_dw

    ids = _case_ids()
    E, bm = 4, 8
    t = ids.shape[0]
    al = moe_align_block_size(ids, E, bm, ragged=True)
    t_pad = al.sorted_token_ids.shape[0]
    live = np.asarray(al.sorted_token_ids) < t
    a = np.array(
        jax.random.normal(jax.random.PRNGKey(7), (t_pad, 32)), np.float32
    )
    g = np.array(
        jax.random.normal(jax.random.PRNGKey(8), (t_pad, 64)), np.float32
    )
    a[~live] = 1e30
    g[~live] = -1e30
    got = np.asarray(group_gemm_dw(
        jnp.asarray(a), jnp.asarray(g), al.expert_ids, E,
        valid_rows=al.valid_rows,
        config=GroupGemmConfig(bm, 64, 32, ragged=True), assume_sorted=True,
    ))
    want = np.zeros((E, 32, 64), np.float32)
    vr = np.asarray(al.valid_rows)
    eids = np.asarray(al.expert_ids)
    for i, e in enumerate(eids):
        v = vr[i]
        if v:
            want[e] += a[i * bm:i * bm + v].T @ g[i * bm:i * bm + v]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    assert np.all(got[1] == 0)  # the zero-row expert stays exactly zero


@pytest.mark.parametrize("chunks", [1, 2])
def test_ag_group_gemm_overlap_ragged(mesh4, chunks, _small_panels):
    """The ragged fused up-projection (legacy and chunked schedules) vs
    the dense golden on live rows, exact zeros on dead rows — and the
    ragged=False config stays bit-exact with the default."""
    from triton_dist_tpu.ops.allgather_group_gemm import ag_group_gemm_overlap

    n, m_loc, topk, n_exp, k_dim, n_loc = 4, 8, 2, 3, 32, 64
    bm = 4
    cfg = GroupGemmConfig(block_m=bm, block_n=32, block_k=32,
                          chunks_per_shard=chunks, ragged=True)
    ka, kb, ki = jax.random.split(jax.random.PRNGKey(21), 3)
    a = jax.random.normal(ka, (n * m_loc, k_dim), jnp.float32)
    b = jax.random.normal(kb, (n_exp, k_dim, n_loc), jnp.float32)
    ids = jax.random.randint(ki, (n * m_loc, topk), 0, n_exp, jnp.int32)

    def run(cfg_, ragged):
        def fn(a_loc, b_loc, ids_all):
            ral = moe_align_ranked(
                ids_all.reshape(n, m_loc * topk), n_exp, bm, m_loc,
                ragged=ragged,
            )
            h = ag_group_gemm_overlap(
                a_loc, b_loc, ral, axis="tp", config=cfg_,
                gather_group_blocks=2,
            )
            return h, ral.local_ids, ral.src_rows, ral.expert_ids

        return jax.jit(
            jax.shard_map(
                fn, mesh=mesh4,
                in_specs=(P("tp", None), P(None, None, None), P(None, None)),
                out_specs=(P(None, None),) * 4,
                check_vma=False,
            )
        )(
            jax.device_put(a, jax.NamedSharding(mesh4, P("tp", None))), b, ids
        )

    out, lids, srows, eids = map(np.asarray, run(cfg, True))
    t_pad_loc = lids.shape[1]
    a_np, b_np = np.asarray(a), np.asarray(b)
    for c in range(n):
        for r in range(t_pad_loc):
            row = out[c * t_pad_loc + r]
            if lids[c, r] >= m_loc * topk:
                np.testing.assert_array_equal(row, 0.0)
                continue
            want = a_np[srows[c, r]] @ b_np[eids[c, r // bm]]
            np.testing.assert_allclose(row, want, rtol=1e-4, atol=1e-4)
    if chunks == 1:
        off = dataclasses.replace(cfg, ragged=False)
        base = GroupGemmConfig(block_m=bm, block_n=32, block_k=32)
        np.testing.assert_array_equal(
            np.asarray(run(off, True)[0]), np.asarray(run(base, False)[0])
        )


# ---------------------------------------------------------------------------
# Chaos: ragged tail blocks add no droppable signal edge
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
    """The ragged chunked pipeline at combine-chunk-engaging scale on a
    2-PE mesh (the shape of test_chunked_a2a's pipeline cells)."""
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
        config=GroupGemmConfig(4, 32, 16), overlap=False,
    )
    out = tp_moe_mlp_op(
        x, w_up, w_down, ids, tw, mesh2, config=cfg, overlap=True
    )
    return np.asarray(golden, np.float32), np.asarray(out, np.float32)


@pytest.mark.chaos
@pytest.mark.parametrize("site", [1, 2])
def test_ragged_chunk_signal_drop_no_new_edge(_chaos_config, site):
    """Dropping a chunk signal under the RAGGED chunked pipeline behaves
    exactly like the padded schedule: either the watchdog trips with a
    ``chunk_wait`` diagnostic (the only droppable edges are the same
    chunk signals — ragged added none) or the data-coupled semaphores
    carry the run to an exact result. Never silent corruption."""
    tdt_config.update(
        timeout_iters=TIMEOUT_ITERS,
        fault_plan=FaultPlan("drop_signal", pe=-1, site=site),
        raise_on_timeout=True,
    )
    cfg = GroupGemmConfig(4, 32, 16, chunks_per_shard=2, ragged=True)
    try:
        golden, out = _chaos_pipeline(cfg)
    except R.DistTimeoutError as e:
        assert e.records, "timeout must carry decoded records"
        kinds = {r["kind"] for r in e.records}
        # the droppable edges are the chunk/barrier/data signals the
        # PADDED schedule already had (records.py kind table) — a
        # ragged-only kind here would mean a new signal edge, which is
        # exactly what must not exist
        assert kinds <= {
            "chunk_wait", "barrier_all", "wait", "signal_wait_until"
        }, kinds
        return
    np.testing.assert_allclose(out, golden, rtol=1e-5, atol=1e-5)


@pytest.mark.chaos
def test_ragged_chunk_signal_dup_never_corrupts(_chaos_config):
    """A duplicated chunk signal under the ragged chunked pipeline must
    end exact or loud (semaphore diagnostic / watchdog) — never silently
    wrong."""
    import re

    tdt_config.update(
        timeout_iters=TIMEOUT_ITERS,
        fault_plan=FaultPlan("dup_signal", pe=-1, site=1),
        raise_on_timeout=True,
    )
    cfg = GroupGemmConfig(4, 32, 16, chunks_per_shard=2, ragged=True)
    try:
        golden, out = _chaos_pipeline(cfg)
    except R.DistTimeoutError as e:
        assert e.records
        return
    except Exception as e:  # noqa: BLE001 — classified, as in test_chaos
        assert re.search(r"semaphore|barrier|race", str(e), re.IGNORECASE), e
        return
    np.testing.assert_allclose(out, golden, rtol=1e-5, atol=1e-5)
