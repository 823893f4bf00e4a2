"""Ragged grouped GEMM through the whole MoE pipelines (kernel tier, split from
test_ragged.py, whose docstring holds the tier structure): ragged vs padded
through the overlapped TP pipeline, ragged x chunks_per_shard, the
``ragged_dot`` sentinel, and the EP pipeline. Model-level cells: each is an
interpreted pipeline on four devices, and a file is one xdist worker's job."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

# ops/__init__ re-exports functions that shadow these submodule names,
# and `import a.b.c as x` binds through the attribute chain
gg_mod = importlib.import_module("triton_dist_tpu.ops.group_gemm")
from triton_dist_tpu.ops.group_gemm import GroupGemmConfig
from triton_dist_tpu.ops.moe_utils import select_experts


@pytest.fixture
def _small_panels(monkeypatch):
    """Shrink the MXU row panel so interpreter-scale blocks (bm=8) still
    exercise multi-panel skipping (2 panels per block)."""
    monkeypatch.setattr(gg_mod, "_PANEL_ROWS", 4)


def test_tp_moe_ragged_matches_padded(mesh4, _small_panels):
    """Full fused pipeline, ragged vs padded: same routing, same math —
    forward AND gradients (the backward's grouped GEMMs and dw consume
    the same map)."""
    from triton_dist_tpu.ops.grads import tp_moe_mlp_grad

    n, m_loc, topk, n_exp, h_dim, f_dim = 4, 8, 2, 3, 32, 64
    m_tot = n * m_loc
    kx, ku, kd, kl = jax.random.split(jax.random.PRNGKey(31), 4)
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

    def run(cfg):
        def fn(x, wu, wd, ids, tw):
            def loss(x, wu, wd):
                out = tp_moe_mlp_grad(
                    x, wu, wd, ids, tw, "tp", jax.nn.gelu, cfg, None, True
                )
                return jnp.sum(out.astype(jnp.float32)), out

            (l, out), grads = jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True
            )(x, wu, wd)
            return out, *grads

        return jax.jit(
            jax.shard_map(
                fn, mesh=mesh4, in_specs=specs,
                out_specs=(P("tp", None), P("tp", None),
                           P(None, None, "tp"), P(None, "tp", None)),
                check_vma=False,
            )
        )(x, w_up, w_down, ids, tw.astype(jnp.float32))

    ragged = run(GroupGemmConfig(4, 32, 32, ragged=True))
    padded = run(GroupGemmConfig(4, 32, 32))
    for r, p in zip(ragged, padded):
        np.testing.assert_allclose(
            np.asarray(r, np.float32), np.asarray(p, np.float32),
            rtol=1e-5, atol=1e-5,
        )


def test_tp_moe_ragged_chunked_composition(mesh4, _small_panels):
    """ragged × chunks_per_shard through the whole overlapped pipeline
    (m_loc=256 engages the combine-side chunk schedule) vs the padded
    sequential composition."""
    from triton_dist_tpu.ops.grads import tp_moe_mlp_grad

    n, m_loc, topk, n_exp, h_dim, f_dim = 4, 256, 1, 2, 16, 32
    m_tot = n * m_loc
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

    def run(overlap, cfg):
        return jax.jit(
            jax.shard_map(
                lambda x, wu, wd, i, t: tp_moe_mlp_grad(
                    x, wu, wd, i, t, "tp", jax.nn.gelu, cfg, None, overlap
                ),
                mesh=mesh4, in_specs=specs, out_specs=P("tp", None),
                check_vma=False,
            )
        )(x, w_up, w_down, ids, tw.astype(jnp.float32))

    # block_m 32: an eighth of the interpreted grid steps of 4, and the
    # experts' segments (a random ~128 rows a PE each) still end mid-block
    fused = np.asarray(run(
        True, GroupGemmConfig(32, 32, 16, chunks_per_shard=2, ragged=True)
    ), np.float32)
    seq = np.asarray(run(False, GroupGemmConfig(32, 32, 16)), np.float32)
    np.testing.assert_allclose(fused, seq, rtol=1e-5, atol=1e-5)


def test_tp_moe_ragged_dot_sentinel(mesh4):
    """The jax.lax.ragged_dot sentinel candidate (backend="ragged_dot")
    runs the pipeline through the sequential composition and matches the
    fused default."""
    from triton_dist_tpu.ops.grads import tp_moe_mlp_op

    m_tot, h_dim, f_dim, n_exp, topk = 16, 32, 64, 3, 2
    kx, ku, kd, kl = jax.random.split(jax.random.PRNGKey(41), 4)
    x = jax.random.normal(kx, (m_tot, h_dim), jnp.float32)
    w_up = jax.random.normal(ku, (n_exp, h_dim, f_dim)) / 8
    w_down = jax.random.normal(kd, (n_exp, f_dim, h_dim)) / 8
    tw, ids = select_experts(
        jax.random.normal(kl, (m_tot, n_exp), jnp.float32), topk
    )
    base = tp_moe_mlp_op(
        x, w_up, w_down, ids, tw, mesh4,
        config=GroupGemmConfig(4, 32, 32), overlap=True,
    )
    sent = tp_moe_mlp_op(
        x, w_up, w_down, ids, tw, mesh4,
        config=GroupGemmConfig(4, 32, 32, backend="ragged_dot"),
        overlap=True,
    )
    np.testing.assert_allclose(
        np.asarray(base), np.asarray(sent), rtol=1e-5, atol=1e-5
    )


def test_ep_moe_ragged_matches_padded(mesh4, _small_panels):
    """EP layer end-to-end: the ragged receiver alignment (virtual
    padding expert skipped outright) reproduces the padded output."""
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

    def run(cfg):
        layer = EPMoEMLP(
            n_experts=n_exp, topk=topk, max_m=max_m, axis="tp",
            gg_config=cfg,
        )
        return jax.jit(
            jax.shard_map(
                lambda x, wu, wd, i, t: layer(x, wu, wd, i, t),
                mesh=mesh4,
                in_specs=(P("tp", None), P("tp", None, None),
                          P("tp", None, None), P("tp", None), P("tp", None)),
                out_specs=P("tp", None), check_vma=False,
            )
        )(x, w_up, w_down, ids, tw)

    padded = np.asarray(run(GroupGemmConfig(4, 32, 16)), np.float32)
    ragged = np.asarray(
        run(GroupGemmConfig(4, 32, 16, ragged=True)), np.float32
    )
    np.testing.assert_allclose(ragged, padded, rtol=1e-5, atol=1e-5)
