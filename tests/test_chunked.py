"""Chunk-granular ring overlap (ISSUE 3): per-chunk DMA signaling for the
fused collective ops.

Three tiers, matching the repo's environment matrix:

- **host-level** (runs everywhere): the chunk schedule math, the
  ``chunk_wait`` record kind codec, the tune-space ordering contract (the
  sweep-free walks can never apply a chunked schedule untimed), the
  per-chunk perf-model terms, the ``ChunkedPutHandle`` bookkeeping, and the
  ``autotuner._sig_key`` prefix-collision fix.
- **kernel-level** (needs a jax line with the fused-op APIs —
  ``jax.lax.axis_size``; on older lines these skip exactly like the
  pre-existing ring-op tests fail-by-seed): non-divisor chunk counts,
  chunk=1 ≡ legacy equivalence, and golden-exactness of every chunked ring
  family.
- **chaos** (needs the Mosaic TPU interpreter): a dropped/duplicated
  *chunk* signal under ``FaultPlan`` either trips the watchdog with a
  diagnostic record naming the chunk wait site (kind ``chunk_wait``) or
  leaves the result exact — never silent corruption.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu import config as tdt_config
from triton_dist_tpu.ops.common import chunk_schedule
from triton_dist_tpu.resilience import FaultPlan
from triton_dist_tpu.resilience import records as R




# ---------------------------------------------------------------------------
# Host-level: the chunk schedule
# ---------------------------------------------------------------------------

def test_chunk_schedule_non_divisor():
    # the ISSUE's canonical case: 3 chunks over a 512-row shard
    spans = chunk_schedule(512, 3)
    assert spans == ((0, 171), (171, 171), (342, 170))
    assert sum(rows for _, rows in spans) == 512
    sizes = [rows for _, rows in spans]
    assert max(sizes) - min(sizes) <= 1  # balanced to within one row
    # spans are contiguous and ordered
    assert all(
        spans[j][0] + spans[j][1] == spans[j + 1][0]
        for j in range(len(spans) - 1)
    )


def test_chunk_schedule_quantum_alignment():
    """GEMM families pass their MXU row tile as the quantum: span
    boundaries align to it, so pick_block never collapses on an odd
    chunk row count (the 1-row-tile cliff)."""
    assert chunk_schedule(512, 3, quantum=128) == (
        (0, 256), (256, 128), (384, 128)
    )
    # sub-quantum tail is absorbed by the last chunk
    assert chunk_schedule(500, 3, quantum=128) == (
        (0, 128), (128, 128), (256, 244)
    )
    # more chunks than quanta clamps to one quantum per chunk
    assert chunk_schedule(256, 8, quantum=128) == ((0, 128), (128, 128))
    # quantum=1 is the default balanced split, bit for bit
    assert chunk_schedule(512, 3, quantum=1) == chunk_schedule(512, 3)

    from triton_dist_tpu.utils import pick_block

    # the ops' quantum formula keeps full tiles at the bench shape:
    # m_loc=1024, block_m=1024, 4 chunks → 4 × 256-row spans, 256-row tiles
    q = pick_block(1024, min(1024, 1024 // 4))
    spans = chunk_schedule(1024, 4, quantum=q)
    assert spans == ((0, 256), (256, 256), (512, 256), (768, 256))
    assert all(pick_block(rows, 1024) == 256 for _, rows in spans)


def test_chunk_schedule_divisor_identity_and_clamp():
    assert chunk_schedule(16, 4) == ((0, 4), (4, 4), (8, 4), (12, 4))
    assert chunk_schedule(16, 1) == ((0, 16),)          # the legacy schedule
    assert chunk_schedule(3, 8) == ((0, 1), (1, 1), (2, 1))  # clamps to rows
    with pytest.raises(ValueError, match="chunks"):
        chunk_schedule(16, 0)
    with pytest.raises(ValueError, match="rows"):
        chunk_schedule(0, 1)


def test_chunk_record_kind_roundtrip():
    """The watchdog's diagnostic record names the chunk wait site."""
    row = [0] * R.DIAG_LEN
    row[R.F_STATUS] = R.STATUS_TIMEOUT
    row[R.F_FAMILY] = R.family_code_for("chunked_family")
    row[R.F_PE] = 1
    row[R.F_SITE] = 2
    row[R.F_KIND] = R.KIND_CHUNK
    row[R.F_EXPECTED] = 1
    rec = R.decode_record(row)
    assert rec["kind"] == "chunk_wait"
    assert rec["site"] == 2
    err = R.DistTimeoutError("chunked_family", [rec])
    assert "chunk_wait" in str(err)


def test_tune_spaces_chunk_axis_ordering():
    """chunks_per_shard is a first-class autotune axis — but every chunked
    candidate sits AFTER every chunk=1 candidate, so the sweep-free walks
    (cached_or_first / interpreter-first-viable) can only ever apply the
    proven legacy schedules untimed: the tuner cannot regress."""
    from triton_dist_tpu.ops.allgather_gemm import AG_GEMM_TUNE_SPACE
    from triton_dist_tpu.ops.gemm_reduce_scatter import GEMM_RS_TUNE_SPACE
    from triton_dist_tpu.ops.reduce_scatter import RS_TUNE_SPACE

    for space in (AG_GEMM_TUNE_SPACE, GEMM_RS_TUNE_SPACE, RS_TUNE_SPACE):
        chunked = [getattr(c, "chunks_per_shard", 1) > 1 for c in space]
        assert any(chunked), "space must sweep the chunk axis"
        first_chunked = chunked.index(True)
        assert not any(chunked[:first_chunked][1:]) and not chunked[0]
        assert all(
            getattr(c, "chunks_per_shard", 1) == 1
            for c in space[:first_chunked]
        )


def test_perf_model_chunked_terms():
    from triton_dist_tpu import perf_model as pm

    spec = pm.CHIP_SPECS["v5e"]
    shard = 1 << 22
    for n in (2, 4, 8):
        # chunks=1 must reproduce the legacy shard-granular model exactly
        assert pm.estimate_ring_chunked_time_ms(shard, n, 1, spec) == (
            pytest.approx(pm.estimate_ag_ring_time_ms(shard, n, spec))
        )
    # the per-chunk bubble term shrinks monotonically with chunk count
    bubbles = [
        pm.estimate_fused_ring_bubble_ms(shard, 8, c, spec)
        for c in (1, 2, 4, 8)
    ]
    assert all(b1 > b2 for b1, b2 in zip(bubbles, bubbles[1:]))
    # large shards on big rings want chunking; tiny shards do not
    assert pm.suggest_chunks_per_shard(shard, 8, spec) > 1
    assert pm.suggest_chunks_per_shard(256, 8, spec) == 1
    assert pm.suggest_chunks_per_shard(shard, 2, spec) == 1
    # world-1 degenerate
    assert pm.estimate_ring_chunked_time_ms(shard, 1, 4, spec) == 0.0
    assert pm.estimate_fused_ring_bubble_ms(shard, 1, 4, spec) == 0.0


class _FakePut:
    """Stand-in for shmem.PutHandle: counts waits, enforces the consuming-
    wait contract (a second send wait would deadlock on hardware)."""

    def __init__(self):
        self.send_waited = False
        self.recv_waits = 0
        self.sig_sem = None

    def wait_send(self):
        assert not self.send_waited, "double send-wait (consuming semantics)"
        self.send_waited = True

    def wait_recv(self):
        self.recv_waits += 1


def test_chunked_put_handle_bookkeeping():
    from triton_dist_tpu.shmem.device import ChunkedPutHandle

    fakes = [_FakePut() for _ in range(3)]
    h = ChunkedPutHandle(fakes)
    assert len(h) == 3
    h.wait_recv_chunk(1)
    assert [f.recv_waits for f in fakes] == [0, 1, 0]
    h.wait_send_chunk(0)
    h.wait_send_chunk(0)  # idempotent: consuming-wait safety
    assert fakes[0].send_waited and not fakes[1].send_waited
    h.wait_send()  # drains the rest, skips the already-waited chunk
    assert all(f.send_waited for f in fakes)
    h.wait_recv()
    assert [f.recv_waits for f in fakes] == [1, 2, 1]


def test_sig_key_no_prefix_collision():
    """Two distinct non-array contexts sharing a 160-char prefix must key
    the autotune cache differently (the old truncation served one context
    the other's cached config)."""
    from triton_dist_tpu.autotuner import _sig_key

    class _Ctx:
        def __init__(self, s):
            self._s = s

        def __str__(self):
            return self._s

    base = "x" * 200
    a = _Ctx(base + "tail-a")
    b = _Ctx(base + "tail-b")
    assert _sig_key((a,), {}) != _sig_key((b,), {})
    # equal contexts still key identically (determinism)
    assert _sig_key((_Ctx(base),), {}) == _sig_key((_Ctx(base),), {})
    # short contexts stay readable verbatim
    assert "my_method" in _sig_key((_Ctx("my_method"),), {})


def test_config_chunk_fields_default_legacy():
    """chunks_per_shard defaults to 1 everywhere — the bit-for-bit legacy
    anchor — and configs stay hashable (jit_shard_map cache keys)."""
    from triton_dist_tpu.ops.allgather_gemm import AGGemmConfig
    from triton_dist_tpu.ops.gemm_reduce_scatter import GemmRSConfig
    from triton_dist_tpu.ops.reduce_scatter import ReduceScatterConfig

    for cls in (AGGemmConfig, GemmRSConfig, ReduceScatterConfig):
        cfg = cls()
        assert cfg.chunks_per_shard == 1
        hash(cfg)  # frozen dataclass: usable as a cache key


# ---------------------------------------------------------------------------
# Kernel-level: chunked schedules vs goldens (interpret mode)
# ---------------------------------------------------------------------------

def test_all_gather_chunked_non_divisor(mesh4):
    """The ISSUE's canonical case live: 3 chunks over a 512-row shard —
    non-divisor spans (171/171/170) must still land every row exactly."""
    from triton_dist_tpu.ops.allgather import all_gather_op

    x = jax.random.normal(jax.random.PRNGKey(0), (4 * 512, 2), jnp.float32)
    out = all_gather_op(x, mesh4, method="ring_1d", chunks_per_shard=3)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_all_gather_chunk1_matches_legacy(mesh4):
    """chunks_per_shard=1 is the legacy schedule bit for bit."""
    from triton_dist_tpu.ops.allgather import all_gather_op

    x = jax.random.normal(jax.random.PRNGKey(1), (4 * 16, 8), jnp.float32)
    legacy = all_gather_op(x, mesh4, method="ring_1d")
    c1 = all_gather_op(x, mesh4, method="ring_1d", chunks_per_shard=1)
    np.testing.assert_array_equal(np.asarray(legacy), np.asarray(c1))
    np.testing.assert_array_equal(np.asarray(legacy), np.asarray(x))


def test_all_gather_bidir_chunked(mesh4):
    from triton_dist_tpu.ops.allgather import all_gather_op

    x = jax.random.normal(jax.random.PRNGKey(2), (4 * 16, 8), jnp.float32)
    out = all_gather_op(x, mesh4, method="ring_bidir", chunks_per_shard=2)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


@pytest.mark.parametrize("chunks", [2, 3])
def test_ag_gemm_chunked(mesh4, chunks):
    """Chunk-granular fused AG-GEMM vs the all_gather+dot golden; chunks=3
    over a 16-row shard exercises non-divisor chunk tiles in the MXU
    pipeline (6/5/5 rows)."""
    from jax.sharding import PartitionSpec as P

    from triton_dist_tpu.ops.allgather_gemm import AGGemmConfig, ag_gemm_op

    m_loc, k, n_total = 16, 128, 256
    a = jax.random.normal(jax.random.PRNGKey(3), (4 * m_loc, k), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(4), (k, n_total), jnp.float32)
    cfg = AGGemmConfig(
        block_m=16, block_n=128, block_k=64, chunks_per_shard=chunks
    )
    got = ag_gemm_op(a, b, mesh4, config=cfg)

    def f(a, b):
        a_full = jax.lax.all_gather(a, "tp", tiled=True)
        return jnp.dot(
            a_full.astype(jnp.float32), b.astype(jnp.float32)
        ).astype(a.dtype)

    want = jax.jit(
        jax.shard_map(
            f, mesh=mesh4, in_specs=(P("tp", None), P(None, "tp")),
            out_specs=P(None, "tp"), check_vma=False,
        )
    )(a, b)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=1e-4, atol=1e-4,
    )


def test_ag_gemm_chunk1_matches_legacy(mesh4):
    """chunks_per_shard=1 reproduces the legacy fused schedule exactly
    (same kernel, bitwise-equal outputs)."""
    from triton_dist_tpu.ops.allgather_gemm import AGGemmConfig, ag_gemm_op

    m_loc, k, n_total = 16, 128, 256
    a = jax.random.normal(jax.random.PRNGKey(5), (4 * m_loc, k), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(6), (k, n_total), jnp.float32)
    legacy = ag_gemm_op(a, b, mesh4, config=AGGemmConfig(16, 128, 64))
    c1 = ag_gemm_op(
        a, b, mesh4, config=AGGemmConfig(16, 128, 64, chunks_per_shard=1)
    )
    np.testing.assert_array_equal(np.asarray(legacy), np.asarray(c1))


def test_gemm_rs_ring_chunked(mesh4):
    from triton_dist_tpu.ops.gemm_reduce_scatter import GemmRSConfig, gemm_rs_op

    m_tot, k_tot, n_dim = 32, 128, 64  # k_loc = 32 per PE
    a = jax.random.normal(jax.random.PRNGKey(7), (m_tot, k_tot), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(8), (k_tot, n_dim), jnp.float32)
    cfg = GemmRSConfig(block_m=8, block_n=64, block_k=32, chunks_per_shard=2)
    got = gemm_rs_op(a, b, mesh4, method="ring", config=cfg)
    want = np.asarray(a, np.float32) @ np.asarray(b, np.float32)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), want, rtol=1e-4, atol=1e-4
    )


@pytest.mark.parametrize("chunks", [2, 3])
def test_reduce_scatter_ring_chunked(mesh4, chunks):
    from triton_dist_tpu.ops.reduce_scatter import (
        ReduceScatterConfig, reduce_scatter_op,
    )

    x = jax.random.normal(jax.random.PRNGKey(9), (4, 32, 16), jnp.float32)
    cfg = ReduceScatterConfig(8, 16, "ring", chunks_per_shard=chunks)
    got = reduce_scatter_op(x, mesh4, config=cfg)
    want = np.asarray(x, np.float32).sum(axis=0)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), want, rtol=1e-5, atol=1e-5
    )


# ---------------------------------------------------------------------------
# Chaos: chunk-signal faults (Mosaic TPU interpreter required)
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
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:2]), ("tp",))


@pytest.mark.chaos
def test_chunk_signal_drop_names_chunk_wait_site(_chaos_config):
    """A dropped per-chunk signal trips the watchdog and the diagnostic
    record names the chunk wait site (kind ``chunk_wait``) — the
    acceptance contract of ISSUE 3's chaos satellite.

    Site arithmetic (world 2): the barrier's single round is signal site
    0, so the step-0 chunk signals occupy sites 1..chunks — dropping site
    1 starves every PE's first chunk wait."""
    from triton_dist_tpu.ops.allgather import all_gather_op

    mesh2 = _mesh2()
    tdt_config.update(
        timeout_iters=TIMEOUT_ITERS,
        fault_plan=FaultPlan("drop_signal", pe=-1, site=1),
        raise_on_timeout=True,
    )
    x = jax.random.normal(jax.random.PRNGKey(20), (2 * 16, 4), jnp.float32)
    with pytest.raises(R.DistTimeoutError) as ei:
        all_gather_op(x, mesh2, method="ring_1d", chunks_per_shard=2)
    assert ei.value.records, "DistTimeoutError must carry decoded records"
    kinds = {r["kind"] for r in ei.value.records}
    assert "chunk_wait" in kinds, ei.value.records


@pytest.mark.chaos
def test_chunk_signal_dup_never_corrupts(_chaos_config):
    """A duplicated chunk signal must end in a correct result or a loud
    semaphore diagnostic — never silent corruption (the over-credit can
    be rejected by the interpreter's exit validation, exactly as for the
    barrier dup cells in tests/test_chaos.py)."""
    import re

    from triton_dist_tpu.ops.allgather import all_gather_op

    mesh2 = _mesh2()
    tdt_config.update(
        timeout_iters=TIMEOUT_ITERS,
        fault_plan=FaultPlan("dup_signal", pe=-1, site=1),
        raise_on_timeout=True,
    )
    x = jax.random.normal(jax.random.PRNGKey(21), (2 * 16, 4), jnp.float32)
    try:
        out = all_gather_op(x, mesh2, method="ring_1d", chunks_per_shard=2)
    except R.DistTimeoutError as e:
        assert e.records
        return
    except Exception as e:  # noqa: BLE001 — classified, as in test_chaos
        assert re.search(r"semaphore|barrier|race", str(e), re.IGNORECASE), e
        return
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
