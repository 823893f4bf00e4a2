"""Chunk-granular EP overlap (ISSUE 4): pipelining the MoE dispatch →
group-GEMM → combine path over the chunked all-to-all.

Three tiers, matching the repo's environment matrix (tests/test_chunked.py):

- **host-level** (runs everywhere): the a2a/MoE tune-space ordering
  contract, the a2a chunked perf-model terms and suggester, the
  ``prune_chunk_candidates`` satellite (pruning never removes the legacy
  candidate), the chunk-major issue order of the peer-direct a2a put, and
  the config plumbing defaults.
- **kernel-level** (needs a jax line with the fused-op APIs —
  ``jax.lax.axis_size``; skips exactly like tests/test_chunked.py's kernel
  tier on older lines): chunked ``fast_all_to_all`` vs the transpose
  golden (incl. non-divisor chunk counts over uneven per-peer row counts),
  chunk=1 ≡ legacy bit-exact, and the chunked MoE pipeline vs the
  sequential composition.
- **chaos** (needs the Mosaic TPU interpreter): a dropped/duplicated a2a
  *chunk* signal under ``FaultPlan`` either trips the watchdog with a
  diagnostic record naming the chunk wait site (kind ``chunk_wait``) or
  leaves the result exact — never silent corruption.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu import config as tdt_config
from triton_dist_tpu import perf_model as pm
from triton_dist_tpu.resilience import FaultPlan
from triton_dist_tpu.resilience import records as R




# ---------------------------------------------------------------------------
# Host-level: tune-space ordering, perf model, pruning, issue order
# ---------------------------------------------------------------------------

def test_a2a_moe_tune_spaces_chunk_axis_ordering():
    """chunks_per_shard is a first-class axis of the a2a and MoE pipeline
    spaces — with every chunked candidate strictly AFTER every chunk=1
    candidate, so the sweep-free walks (cached_or_first /
    interpreter-first-viable) can only ever apply the proven legacy
    schedules untimed: the tuner cannot regress (the PR 3 invariant,
    extended to the EP family)."""
    from triton_dist_tpu.ops.all_to_all import A2A_TUNE_SPACE
    from triton_dist_tpu.ops.grads import TP_MOE_TUNE_SPACE

    for space in (A2A_TUNE_SPACE, TP_MOE_TUNE_SPACE):
        chunked = [getattr(c, "chunks_per_shard", 1) > 1 for c in space]
        assert any(chunked), "space must sweep the chunk axis"
        first_chunked = chunked.index(True)
        assert all(chunked[first_chunked:]), "chunked candidates must be last"
        assert not any(chunked[:first_chunked])


def test_perf_model_a2a_chunked_terms():
    spec = pm.CHIP_SPECS["v5e"]
    slab = 1 << 21
    for n in (2, 4, 8):
        # chunks=1 must reproduce the legacy a2a model plus the single
        # issue/hop latency, exactly
        assert pm.estimate_a2a_chunked_time_ms(slab, n, 1, spec) == (
            pytest.approx(
                pm.estimate_all_to_all_time_ms(slab, n, spec)
                + pm.ICI_HOP_LATENCY_MS
            )
        )
    # the exposed dispatch bubble shrinks monotonically with chunk count
    bubbles = [
        pm.estimate_a2a_chunk_bubble_ms(slab, 8, c, spec)
        for c in (1, 2, 4, 8)
    ]
    assert all(b1 > b2 for b1, b2 in zip(bubbles, bubbles[1:]))
    # big dispatch slabs want chunking; tiny (latency-bound) slabs do not
    assert pm.suggest_a2a_chunks_per_shard(slab, 8, spec) > 1
    assert pm.suggest_a2a_chunks_per_shard(256, 8, spec) == 1
    # world-1 degenerates
    assert pm.estimate_a2a_chunked_time_ms(slab, 1, 4, spec) == 0.0
    assert pm.estimate_a2a_chunk_bubble_ms(slab, 1, 4, spec) == 0.0
    assert pm.suggest_a2a_chunks_per_shard(slab, 1, spec) == 1


def test_prune_chunk_candidates_never_removes_legacy():
    """The ISSUE 4 satellite contract: model-driven pruning may drop
    dominated CHUNKED candidates, but the chunk=1 legacy candidates always
    survive, in their original (leading) positions — so the sweep-free
    walks keep their proven anchor whatever the model says."""
    from triton_dist_tpu.ops.all_to_all import A2A_TUNE_SPACE

    spec = pm.CHIP_SPECS["v5e"]
    legacy = tuple(
        c for c in A2A_TUNE_SPACE if getattr(c, "chunks_per_shard", 1) <= 1
    )
    # tiny slab: the suggester says 1, every chunked candidate is pruned —
    # and the survivors are exactly the legacy candidates, in order
    pruned_tiny = pm.prune_chunk_candidates(
        A2A_TUNE_SPACE, 256, 8, spec, suggest=pm.suggest_a2a_chunks_per_shard
    )
    assert pruned_tiny == legacy
    # big slab: chunked candidates within 2x the suggestion survive, and
    # the legacy prefix is untouched
    pruned_big = pm.prune_chunk_candidates(
        A2A_TUNE_SPACE, 1 << 21, 8, spec,
        suggest=pm.suggest_a2a_chunks_per_shard,
    )
    assert pruned_big[: len(legacy)] == legacy
    assert any(
        getattr(c, "chunks_per_shard", 1) > 1 for c in pruned_big
    )
    # the ring-model default suggester upholds the same contract
    assert pm.prune_chunk_candidates(A2A_TUNE_SPACE, 16, 2)[: len(legacy)] == (
        legacy
    )


def test_a2a_chunk_preconditions_keep_legacy():
    """The tune-space wiring (precondition hooks): the model may veto a
    chunked candidate for a given problem, never a chunk=1 one."""
    from triton_dist_tpu.ops.all_to_all import A2AConfig, _a2a_chunk_sensible
    from triton_dist_tpu.ops.grads import _moe_block_sensible
    from triton_dist_tpu.ops.group_gemm import GroupGemmConfig

    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    tiny = jnp.zeros((1, 1, 16, 8), jnp.bfloat16)
    assert _a2a_chunk_sensible(A2AConfig(1), tiny, None, mesh)
    assert _a2a_chunk_sensible(A2AConfig(4), tiny, None, mesh)
    assert not _a2a_chunk_sensible(
        A2AConfig(chunks_per_shard=4), tiny, None, mesh
    )
    x = jnp.zeros((64, 64), jnp.bfloat16)
    wu = jnp.zeros((8, 64, 128), jnp.bfloat16)
    ids = jnp.zeros((64, 2), jnp.int32)
    assert _moe_block_sensible(
        GroupGemmConfig(128, 512, 512), x, wu, None, ids, None, mesh
    )
    assert not _moe_block_sensible(
        GroupGemmConfig(128, 512, 512, chunks_per_shard=4),
        x, wu, None, ids, None, mesh,
    )


def test_a2a_put_chunk_major_issue_order(monkeypatch):
    """The peer-direct chunked put issues CHUNK-MAJOR: every peer's chunk
    j starts before any peer's chunk j+1 (first chunks land everywhere
    soonest), and each peer's handle aggregates its chunks in span
    order."""
    from triton_dist_tpu.shmem import device as shmem

    issued = []

    class _Fake:
        def __init__(self, tag):
            self.tag = tag
            self.send_waited = False
            self.sig_sem = None

    def fake_put2(dst, src, pe, axis, send, recv, sig=None):
        issued.append((pe, src))
        return _Fake((pe, src))

    monkeypatch.setattr(shmem, "putmem_signal2_nbi_block", fake_put2)
    spans = ((0, 3), (3, 3), (6, 2))
    peers = [1, 2, 3]
    handles = shmem.putmem_signal_chunked_a2a_nbi_block(
        lambda i, off, rows: ("dst", i, off),
        lambda i, off, rows: ("src", i, off),
        peers, "tp",
        lambda i, j: ("send", i, j),
        lambda i, j: ("recv", i, j),
        None,
        spans,
    )
    assert [pe for pe, _ in issued] == [1, 2, 3, 1, 2, 3, 1, 2, 3]
    offs = [src[2] for _, src in issued]
    assert offs == [0, 0, 0, 3, 3, 3, 6, 6, 6]
    assert len(handles) == 3 and all(len(h) == 3 for h in handles)
    # per-peer handles carry that peer's chunks in span order
    assert handles[1].chunks[2].tag == (2, ("src", 1, 6))


def test_a2a_and_moe_configs_default_legacy():
    """chunks_per_shard defaults to 1 everywhere — the bit-for-bit legacy
    anchor — and configs stay hashable (jit_shard_map cache keys)."""
    from triton_dist_tpu.ops.all_to_all import A2AConfig
    from triton_dist_tpu.ops.group_gemm import GroupGemmConfig

    for cls in (A2AConfig, GroupGemmConfig):
        cfg = cls()
        assert cfg.chunks_per_shard == 1
        hash(cfg)
    # EP layers thread the knob without mutating defaults
    from triton_dist_tpu.layers.ep_a2a_layer import EPAll2AllLayer
    from triton_dist_tpu.layers.ep_moe_mlp import EPMoEMLP

    assert EPAll2AllLayer(n_experts=4, topk=2, max_m=8).a2a_config is None
    assert EPMoEMLP(n_experts=4, topk=2, max_m=8).a2a_config is None


def test_combine_chunk_schedule_tile_aligned():
    """The combine-side push schedule quantizes to 128 rows so chunk
    boundaries stay tile-aligned for any dtype; sub-quantum problems
    collapse to one span (→ the legacy kernel)."""
    from triton_dist_tpu.ops.common import chunk_schedule

    spans = chunk_schedule(1024, 4, quantum=128)
    assert spans == ((0, 256), (256, 256), (512, 256), (768, 256))
    assert all(off % 128 == 0 for off, _ in spans)
    assert chunk_schedule(200, 4, quantum=128) == ((0, 200),)
    # non-divisor: the tail rides the last chunk, boundaries stay aligned
    spans = chunk_schedule(640, 4, quantum=128)
    assert sum(r for _, r in spans) == 640
    assert all(off % 128 == 0 for off, _ in spans)


# ---------------------------------------------------------------------------
# Kernel-level: chunked schedules vs goldens (interpret mode)
# ---------------------------------------------------------------------------

def _a2a_case(key, n, max_m, hidden, uneven=False):
    kd, ks = jax.random.split(key)
    tokens = jax.random.normal(kd, (n, n, max_m, hidden), jnp.float32)
    if uneven:
        splits = jax.random.randint(ks, (n, n), 0, max_m + 1, jnp.int32)
    else:
        splits = jnp.full((n, n), max_m, jnp.int32)
    return tokens, splits


@pytest.mark.parametrize("chunks", [2, 3])
def test_fast_all_to_all_chunked(mesh4, chunks):
    """Chunk-granular a2a vs the transpose golden; chunks=3 over max_m=8
    exercises non-divisor spans (3/3/2 rows)."""
    from triton_dist_tpu.ops.all_to_all import A2AConfig, fast_all_to_all_op

    tokens, splits = _a2a_case(jax.random.PRNGKey(30), 4, 8, 128)
    recv, rsplits = fast_all_to_all_op(
        tokens, splits, mesh4, config=A2AConfig(chunks_per_shard=chunks)
    )
    want = np.asarray(tokens).transpose(1, 0, 2, 3)
    np.testing.assert_array_equal(np.asarray(recv), want)
    np.testing.assert_array_equal(np.asarray(rsplits), np.asarray(splits).T)


def test_fast_all_to_all_chunked_uneven_splits(mesh4):
    """Non-divisor chunk counts over UNEVEN per-peer row counts: the slab
    contract ships full padded slabs whatever the valid counts, so the
    exchange must stay exact row-for-row."""
    from triton_dist_tpu.ops.all_to_all import A2AConfig, fast_all_to_all_op

    tokens, splits = _a2a_case(jax.random.PRNGKey(31), 4, 8, 128, uneven=True)
    recv, rsplits = fast_all_to_all_op(
        tokens, splits, mesh4, config=A2AConfig(chunks_per_shard=3)
    )
    want = np.asarray(tokens).transpose(1, 0, 2, 3)
    np.testing.assert_array_equal(np.asarray(recv), want)
    np.testing.assert_array_equal(np.asarray(rsplits), np.asarray(splits).T)


def test_fast_all_to_all_chunk1_matches_legacy(mesh4):
    """chunks_per_shard=1 dispatches to the unchanged legacy kernel — the
    exchange is bit-for-bit the default config's."""
    from triton_dist_tpu.ops.all_to_all import A2AConfig, fast_all_to_all_op

    tokens, splits = _a2a_case(jax.random.PRNGKey(32), 4, 8, 128)
    legacy, ls = fast_all_to_all_op(
        tokens, splits, mesh4, config=A2AConfig()
    )
    c1, cs = fast_all_to_all_op(
        tokens, splits, mesh4, config=A2AConfig(chunks_per_shard=1)
    )
    np.testing.assert_array_equal(np.asarray(legacy), np.asarray(c1))
    np.testing.assert_array_equal(np.asarray(ls), np.asarray(cs))


def test_ep_layer_chunked_roundtrip(mesh4):
    """EPAll2AllLayer with a chunked transport: dispatch + combine must
    reproduce the legacy layer's output exactly (same slab contract, same
    routing bookkeeping — only the wire schedule differs)."""
    from jax.sharding import PartitionSpec as P

    from triton_dist_tpu.layers.ep_a2a_layer import EPAll2AllLayer
    from triton_dist_tpu.ops.all_to_all import A2AConfig

    n, m_loc, hidden, n_exp, topk, max_m = 4, 8, 32, 8, 2, 16
    kx, ki, kw = jax.random.split(jax.random.PRNGKey(33), 3)
    x = jax.random.normal(kx, (n * m_loc, hidden), jnp.float32)
    ids = jax.random.randint(ki, (n * m_loc, topk), 0, n_exp, jnp.int32)
    tw = jax.nn.softmax(
        jax.random.normal(kw, (n * m_loc, topk), jnp.float32), axis=-1
    )

    def run(cfg):
        layer = EPAll2AllLayer(
            n_experts=n_exp, topk=topk, max_m=max_m, axis="tp",
            a2a_config=cfg,
        )

        def fn(x, ids, tw):
            recv, info = layer.dispatch(x, ids)
            # identity "expert": combine returns the weighted sum of the
            # token's own copies — a pure transport roundtrip
            return layer.combine(recv, info, tw, m_loc)

        return jax.jit(
            jax.shard_map(
                fn, mesh=mesh4,
                in_specs=(P("tp", None), P("tp", None), P("tp", None)),
                out_specs=P("tp", None), check_vma=False,
            )
        )(x, ids, tw)

    legacy = np.asarray(run(None))
    chunked = np.asarray(run(A2AConfig(chunks_per_shard=2)))
    np.testing.assert_array_equal(legacy, chunked)


def test_ag_group_gemm_overlap_chunked(mesh4):
    """The chunked fused up-projection (ring chunks consumed group by
    group) vs the dense golden — gather_group_blocks=2 forces several
    groups per rank slab so the chunk schedule actually engages."""
    from triton_dist_tpu.ops.allgather_group_gemm import ag_group_gemm_overlap
    from triton_dist_tpu.ops.group_gemm import GroupGemmConfig
    from triton_dist_tpu.ops.moe_utils import moe_align_ranked

    n, m_loc, topk, n_exp, k_dim, n_loc = 4, 8, 2, 3, 32, 64
    bm = 4
    cfg = GroupGemmConfig(block_m=bm, block_n=32, block_k=32,
                          chunks_per_shard=2)
    ka, kb, ki = jax.random.split(jax.random.PRNGKey(34), 3)
    a = jax.random.normal(ka, (n * m_loc, k_dim), jnp.float32)
    b = jax.random.normal(kb, (n_exp, k_dim, n_loc), jnp.float32)
    ids = jax.random.randint(ki, (n * m_loc, topk), 0, n_exp, jnp.int32)

    def fn(a_loc, b_loc, ids_all):
        ral = moe_align_ranked(
            ids_all.reshape(n, m_loc * topk), n_exp, bm, m_loc
        )
        h = ag_group_gemm_overlap(
            a_loc, b_loc, ral, axis="tp", config=cfg, gather_group_blocks=2
        )
        return h, ral.local_ids, ral.src_rows, ral.expert_ids

    from jax.sharding import PartitionSpec as P

    out, lids, srows, eids = jax.jit(
        jax.shard_map(
            fn, mesh=mesh4,
            in_specs=(P("tp", None), P(None, None, "tp"), P("tp", None)),
            out_specs=(P(None, "tp"), P(None), P(None), P(None)),
            check_vma=False,
        )
    )(a, b, ids)
    out = np.asarray(out, np.float32)
    a_np = np.asarray(a, np.float32)
    b_np = np.asarray(b, np.float32)
    lids = np.asarray(lids)
    srows = np.asarray(srows)
    eids = np.asarray(eids)
    t_pad_loc = lids.shape[1]
    for c in range(n):
        for r in range(t_pad_loc):
            if lids[c, r] >= m_loc * topk:
                continue
            want = a_np[srows[c, r]] @ b_np[eids[c, r // bm]]
            np.testing.assert_allclose(
                out[c * t_pad_loc + r], want, rtol=1e-4, atol=1e-4
            )


def test_tp_moe_pipeline_chunked_matches_sequential(mesh4):
    """The full chunked MoE pipeline (dispatch → group-GEMM → combine over
    chunk-granular transfers) vs the sequential composition: same routing,
    same math. m_loc=256 engages the combine-side chunk schedule (128-row
    quantum); smaller worlds collapse it to the legacy kernel, which the
    chunk1 test below pins."""
    from triton_dist_tpu.ops.grads import tp_moe_mlp_grad
    from triton_dist_tpu.ops.group_gemm import GroupGemmConfig
    from triton_dist_tpu.ops.moe_utils import select_experts
    from jax.sharding import PartitionSpec as P

    n, m_loc, topk, n_exp, h_dim, f_dim = 4, 256, 1, 2, 16, 32
    m_tot = n * m_loc
    cfg = GroupGemmConfig(block_m=32, block_n=32, block_k=16,
                          chunks_per_shard=2)
    kx, ku, kd, kl = jax.random.split(jax.random.PRNGKey(35), 4)
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

    def run(overlap, gg):
        def fn(x, wu, wd, ids, tw):
            return tp_moe_mlp_grad(
                x, wu, wd, ids, tw, "tp", jax.nn.gelu, gg, None, overlap
            )

        return jax.jit(
            jax.shard_map(
                fn, mesh=mesh4, in_specs=specs, out_specs=P("tp", None),
                check_vma=False,
            )
        )(x, w_up, w_down, ids, tw.astype(jnp.float32))

    fused = np.asarray(run(True, cfg), np.float32)
    seq = np.asarray(run(False, cfg), np.float32)
    np.testing.assert_allclose(fused, seq, rtol=1e-5, atol=1e-5)


def test_tp_moe_pipeline_chunk1_matches_legacy(mesh4):
    """chunks_per_shard=1 routes the whole pipeline through the unchanged
    legacy kernels — bit-for-bit against the default config."""
    from triton_dist_tpu.ops.grads import tp_moe_mlp_op
    from triton_dist_tpu.ops.group_gemm import GroupGemmConfig
    from triton_dist_tpu.ops.moe_utils import select_experts

    m_tot, h_dim, f_dim, n_exp, topk = 16, 32, 64, 3, 2
    kx, ku, kd, kl = jax.random.split(jax.random.PRNGKey(36), 4)
    x = jax.random.normal(kx, (m_tot, h_dim), jnp.float32)
    w_up = jax.random.normal(ku, (n_exp, h_dim, f_dim)) / 8
    w_down = jax.random.normal(kd, (n_exp, f_dim, h_dim)) / 8
    tw, ids = select_experts(
        jax.random.normal(kl, (m_tot, n_exp), jnp.float32), topk
    )
    mesh4_ = mesh4
    legacy = tp_moe_mlp_op(
        x, w_up, w_down, ids, tw, mesh4_,
        config=GroupGemmConfig(4, 32, 32), overlap=True,
    )
    c1 = tp_moe_mlp_op(
        x, w_up, w_down, ids, tw, mesh4_,
        config=GroupGemmConfig(4, 32, 32, chunks_per_shard=1), overlap=True,
    )
    np.testing.assert_array_equal(np.asarray(legacy), np.asarray(c1))


# ---------------------------------------------------------------------------
# Chaos: a2a chunk-signal faults (Mosaic TPU interpreter required)
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


def _mesh2():
    return Mesh(np.array(jax.devices()[:2]), ("tp",))


@pytest.mark.chaos
def test_a2a_chunk_signal_drop_names_chunk_wait_site(_chaos_config):
    """A dropped per-chunk a2a signal trips the watchdog and the
    diagnostic record names the chunk wait site (kind ``chunk_wait``) —
    the acceptance contract of ISSUE 4's chaos satellite.

    Site arithmetic (world 2): the barrier's single round is signal site
    0; the chunk-major put rounds occupy sites 1..(n-1)*chunks — dropping
    site 1 starves every PE's first chunk wait."""
    from triton_dist_tpu.ops.all_to_all import A2AConfig, fast_all_to_all_op

    mesh2 = _mesh2()
    tdt_config.update(
        timeout_iters=TIMEOUT_ITERS,
        fault_plan=FaultPlan("drop_signal", pe=-1, site=1),
        raise_on_timeout=True,
    )
    tokens, splits = _a2a_case(jax.random.PRNGKey(40), 2, 8, 16)
    with pytest.raises(R.DistTimeoutError) as ei:
        fast_all_to_all_op(
            tokens, splits, mesh2, config=A2AConfig(chunks_per_shard=2)
        )
    assert ei.value.records, "DistTimeoutError must carry decoded records"
    kinds = {r["kind"] for r in ei.value.records}
    assert "chunk_wait" in kinds, ei.value.records


@pytest.mark.chaos
def test_a2a_chunk_signal_dup_never_corrupts(_chaos_config):
    """A duplicated a2a chunk signal must end in a correct exchange or a
    loud semaphore diagnostic — never silent corruption (the data-coupled
    recv semaphores stay authoritative; the over-credit can be rejected
    by the interpreter's exit validation, as in tests/test_chaos.py)."""
    import re

    from triton_dist_tpu.ops.all_to_all import A2AConfig, fast_all_to_all_op

    mesh2 = _mesh2()
    tdt_config.update(
        timeout_iters=TIMEOUT_ITERS,
        fault_plan=FaultPlan("dup_signal", pe=-1, site=1),
        raise_on_timeout=True,
    )
    tokens, splits = _a2a_case(jax.random.PRNGKey(41), 2, 8, 16)
    try:
        recv, rsplits = fast_all_to_all_op(
            tokens, splits, mesh2, config=A2AConfig(chunks_per_shard=2)
        )
    except R.DistTimeoutError as e:
        assert e.records
        return
    except Exception as e:  # noqa: BLE001 — classified, as in test_chaos
        assert re.search(r"semaphore|barrier|race", str(e), re.IGNORECASE), e
        return
    want = np.asarray(tokens).transpose(1, 0, 2, 3)
    np.testing.assert_array_equal(np.asarray(recv), want)
