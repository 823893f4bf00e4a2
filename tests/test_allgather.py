"""AllGather vs the XLA golden (≙ reference test_ag_gemm.py correctness
pattern: golden = NCCL all_gather_into_tensor; here jax.lax.all_gather).
Inputs are re-randomized across iterations (reference poisons workspaces,
test_ag_gemm.py:120) to surface stale-data bugs."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.ops.allgather import all_gather, all_gather_op


@pytest.mark.parametrize("method", ["ring_1d", "ring_bidir", "full_mesh_push"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_all_gather_methods(mesh8, method, dtype):
    # NOTE: keep per-PE chunks <= ~8 KiB — the TPU interpreter deadlocks on
    # concurrent large DMAs when the host has few cores (see conftest).
    m, d = 16, 128
    fn = jax.jit(
        jax.shard_map(
            functools.partial(all_gather, axis="tp", method=method),
            mesh=mesh8,
            in_specs=P("tp"),
            out_specs=P(None),
            check_vma=False,
        )
    )
    for it in range(3):
        x = jax.random.normal(jax.random.PRNGKey(it), (8 * m, d)).astype(dtype)
        out = fn(x)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


@pytest.mark.parametrize("method", ["ring_1d", "ring_bidir", "full_mesh_push"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_all_gather_rows_off_the_sublane_tile(mesh4, method, dtype):
    """258 rows per PE — the flash-decode combine's payload at Llama-8B
    widths (b*hq + ceil(b*hq/d)) — is not a whole number of 8-row (f32) or
    16-row (bf16) sublane tiles. On the chip an unpadded slot at the
    dynamic offset ``me*258`` halted the four-chip decode step (PR 23);
    ``all_gather`` pads the rows and slices them back. Interpret mode
    cannot show the halt, so this pins the pad/slice arithmetic against
    ``lax.all_gather``; ``tests/test_chip_compile.py`` holds the compile."""
    m, d = 258, 8   # <= ~8 KiB per PE (see above)

    def both(x):
        return (
            all_gather(x, axis="tp", method=method),
            jax.lax.all_gather(x, "tp", tiled=True),
        )

    fn = jax.jit(
        jax.shard_map(
            both, mesh=mesh4, in_specs=P("tp"), out_specs=(P(None), P(None)),
            check_vma=False,
        )
    )
    x = jax.random.normal(jax.random.PRNGKey(7), (4 * m, d)).astype(dtype)
    out, want = fn(x)
    assert out.shape == want.shape == (4 * m, d) and out.dtype == dtype
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("method", ["ring_1d", "ring_bidir", "full_mesh_push"])
def test_all_gather_smaller_world(mesh4, method):
    m, d = 8, 128
    x = jax.random.normal(jax.random.PRNGKey(0), (4 * m, d), jnp.float32)
    out = all_gather_op(x, mesh4, axis="tp", method=method)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_all_gather_world1():
    import jax

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("tp",))
    x = jnp.ones((8, 128), jnp.float32)
    out = all_gather_op(x, mesh, axis="tp")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_all_gather_3d(mesh8):
    """Gather of a rank-3 activation tensor (batch, seq, hidden)."""
    x = jax.random.normal(jax.random.PRNGKey(1), (8 * 2, 8, 128), jnp.float32)
    out = all_gather_op(x, mesh8, axis="tp", method="ring_1d")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_all_gather_on_subaxis(mesh2x4):
    """Gather along 'tp' of a 2-D (dp, tp) mesh — PE addressing must stay
    within the row (team semantics)."""
    m, d = 8, 128

    def fn(x):
        return all_gather(x, axis="tp", method="ring_1d")

    x = jax.random.normal(jax.random.PRNGKey(2), (2 * 4 * m, d), jnp.float32)
    out = jax.jit(
        jax.shard_map(fn, mesh=mesh2x4, in_specs=P(("dp", "tp")), out_specs=P("dp"), check_vma=False)
    )(x)
    got = np.asarray(out).reshape(2, 4 * m, d)
    want = np.asarray(x).reshape(2, 4 * m, d)
    np.testing.assert_array_equal(got, want)


def test_all_gather_2d(mesh2x4):
    """Fused hierarchical 2-D ring over (dp, tp) vs the composite-axis XLA
    golden (VERDICT r1 item 4: multi-axis collectives on mesh2x4)."""
    from triton_dist_tpu.ops.allgather import all_gather_2d

    m, d = 8, 128

    def fn(x):
        return all_gather_2d(x, axes=("dp", "tp"))

    def golden(x):
        return jax.lax.all_gather(x, ("dp", "tp"), tiled=True)

    for it in range(3):
        x = jax.random.normal(jax.random.PRNGKey(10 + it), (8 * m, d), jnp.float32)
        out = jax.jit(
            jax.shard_map(fn, mesh=mesh2x4, in_specs=P(("dp", "tp")), out_specs=P(None), check_vma=False)
        )(x)
        ref = jax.jit(
            jax.shard_map(golden, mesh=mesh2x4, in_specs=P(("dp", "tp")), out_specs=P(None), check_vma=False)
        )(x)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_all_gather_3d(mesh2x2x2):
    """3-axis staged hierarchy (≙ the reference's 3-D node×numa×gpu push,
    low_latency_allgather.py:401) vs the composite-axis XLA golden."""
    from triton_dist_tpu.ops.allgather import all_gather

    m, d = 4, 64

    def fn(x):
        return all_gather(x, axis=("a", "b", "c"))

    def golden(x):
        return jax.lax.all_gather(x, ("a", "b", "c"), tiled=True)

    x = jax.random.normal(jax.random.PRNGKey(40), (8 * m, d), jnp.float32)
    out = jax.jit(
        jax.shard_map(fn, mesh=mesh2x2x2, in_specs=P(("a", "b", "c")),
                      out_specs=P(None), check_vma=False)
    )(x)
    ref = jax.jit(
        jax.shard_map(golden, mesh=mesh2x2x2, in_specs=P(("a", "b", "c")),
                      out_specs=P(None), check_vma=False)
    )(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_all_gather_2d_outer_inner_swapped(mesh2x4):
    """(tp, dp) ordering: outer=tp (4), inner=dp (2) — exercises n_i < n_o."""
    from triton_dist_tpu.ops.allgather import all_gather_2d

    m, d = 8, 128

    def fn(x):
        return all_gather_2d(x, axes=("tp", "dp"))

    def golden(x):
        return jax.lax.all_gather(x, ("tp", "dp"), tiled=True)

    x = jax.random.normal(jax.random.PRNGKey(20), (8 * m, d), jnp.float32)
    out = jax.jit(
        jax.shard_map(fn, mesh=mesh2x4, in_specs=P(("tp", "dp")), out_specs=P(None), check_vma=False)
    )(x)
    ref = jax.jit(
        jax.shard_map(golden, mesh=mesh2x4, in_specs=P(("tp", "dp")), out_specs=P(None), check_vma=False)
    )(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
