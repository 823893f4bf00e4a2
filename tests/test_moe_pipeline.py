"""The whole TP-MoE MLP pipeline vs its sequential composition and its
goldens (split from test_moe.py, which keeps the single ops): overlapped vs
sequential per dtype, the host-level op entry, pre-quantized scales, and
edge routings. Model-level cells: each is an interpreted pipeline on four
devices, and a file is one xdist worker's job."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.ops.group_gemm import GroupGemmConfig
from triton_dist_tpu.ops.moe_utils import select_experts


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tp_moe_overlap_matches_sequential(mesh4, dtype):
    """Fused pair (overlap=True) vs sequential composition (overlap=False)
    of the full MoE TP MLP forward: identical routing, same math."""
    from triton_dist_tpu.ops.grads import tp_moe_mlp_grad
    from triton_dist_tpu.ops.moe_utils import select_experts

    n, m_loc, topk, n_exp, h_dim, f_dim = 4, 8, 2, 3, 32, 64
    m_tot = n * m_loc
    cfg = GroupGemmConfig(block_m=4, block_n=32, block_k=32)
    kx, ku, kd, kl = jax.random.split(jax.random.PRNGKey(13), 4)
    x = jax.random.normal(kx, (m_tot, h_dim)).astype(dtype)
    w_up = (jax.random.normal(ku, (n_exp, h_dim, f_dim)) / 8).astype(dtype)
    w_down = (jax.random.normal(kd, (n_exp, f_dim, h_dim)) / 8).astype(dtype)
    tw, ids = select_experts(
        jax.random.normal(kl, (m_tot, n_exp), jnp.float32), topk
    )
    specs = (
        P("tp", None), P(None, None, "tp"), P(None, "tp", None),
        P("tp", None), P("tp", None),
    )

    def run(overlap):
        def fn(x, wu, wd, ids, tw):
            return tp_moe_mlp_grad(
                x, wu, wd, ids, tw, "tp", jax.nn.gelu, cfg, None, overlap
            )

        return jax.jit(
            jax.shard_map(
                fn, mesh=mesh4, in_specs=specs, out_specs=P("tp", None),
                check_vma=False,
            )
        )(x, w_up, w_down, ids, tw.astype(jnp.float32))

    fused = np.asarray(run(True), np.float32)
    seq = np.asarray(run(False), np.float32)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(fused, seq, rtol=tol, atol=tol)


def test_tp_moe_mlp_op_entry(mesh4):
    """The autotuned host-level MoE MLP entry (the tuner A/Bs the two): fused
    and sequential variants agree through the public sharded interface."""
    from triton_dist_tpu.ops.grads import tp_moe_mlp_op
    from triton_dist_tpu.ops.moe_utils import select_experts

    m_tot, h_dim, f_dim, n_exp, topk = 16, 32, 64, 3, 2
    kx, ku, kd, kl = jax.random.split(jax.random.PRNGKey(23), 4)
    x = jax.random.normal(kx, (m_tot, h_dim), jnp.float32)
    w_up = jax.random.normal(ku, (n_exp, h_dim, f_dim)) / 8
    w_down = jax.random.normal(kd, (n_exp, f_dim, h_dim)) / 8
    tw, ids = select_experts(
        jax.random.normal(kl, (m_tot, n_exp), jnp.float32), topk
    )
    cfg = GroupGemmConfig(4, 32, 32)
    fused = tp_moe_mlp_op(
        x, w_up, w_down, ids, tw, mesh4, config=cfg, overlap=True
    )
    seq = tp_moe_mlp_op(
        x, w_up, w_down, ids, tw, mesh4, config=cfg, overlap=False
    )
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(seq), rtol=1e-5, atol=1e-5
    )


def test_tp_moe_mlp_prequantized_scales(mesh4):
    """ISSUE 8 satellite (the PR 7 noted follow-up): pre-quantized w8
    ``scale=`` operands plumbed through the tp_moe custom_vjp, so
    single-pass serving callers skip ``resolve_w8``'s on-the-fly quantize
    bank read+write.

    Pins: (a) world-1 — explicit (int8, scale) operands from
    ``quantize_expert_weights`` match the ``cfg.w8`` on-the-fly path over
    the same float banks to ULP-level tolerance (same quantizer, same
    values; only XLA fusion of the in-jit quantize differs); (b) the
    sharded mesh4 path stays within weight-quantization tolerance of f32
    (sharding w_down's K dim makes per-shard vs whole-bank scales differ
    legitimately); (c) the straight-through backward runs on int8 banks
    and yields ZERO scale cotangents; (d) int8-without-scales and
    one-scale-only stay loud."""
    from jax.sharding import Mesh

    from triton_dist_tpu.ops.grads import tp_moe_mlp_grad, tp_moe_mlp_op
    from triton_dist_tpu.ops.common import _shard_map
    from triton_dist_tpu.ops.group_gemm import quantize_expert_weights
    from triton_dist_tpu.ops.moe_utils import select_experts

    m_tot, h_dim, f_dim, n_exp, topk = 16, 32, 64, 3, 2
    kx, ku, kd, kl = jax.random.split(jax.random.PRNGKey(24), 4)
    x = jax.random.normal(kx, (m_tot, h_dim), jnp.float32)
    w_up = jax.random.normal(ku, (n_exp, h_dim, f_dim)) / 8
    w_down = jax.random.normal(kd, (n_exp, f_dim, h_dim)) / 8
    tw, ids = select_experts(
        jax.random.normal(kl, (m_tot, n_exp), jnp.float32), topk
    )
    cfg = GroupGemmConfig(4, 32, 32, w8=True)
    wu_q, us = quantize_expert_weights(w_up)
    wd_q, ds = quantize_expert_weights(w_down)

    # (a) world-1: whole banks per PE -> on-the-fly quantize sees exactly
    # the arrays we pre-quantized; outputs must be bit-identical
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("tp",))
    fly = tp_moe_mlp_op(x, w_up, w_down, ids, tw, mesh1, config=cfg)
    pre = tp_moe_mlp_op(
        x, wu_q, wd_q, ids, tw, mesh1, config=cfg,
        w_up_scale=us, w_down_scale=ds,
    )
    np.testing.assert_allclose(
        np.asarray(fly), np.asarray(pre), rtol=1e-4, atol=1e-6
    )

    # (b) sharded path: explicit scales through the spec plumbing, within
    # quantization tolerance of the f32 pipeline
    f32_cfg = GroupGemmConfig(4, 32, 32)
    want = np.asarray(
        tp_moe_mlp_op(x, w_up, w_down, ids, tw, mesh4, config=f32_cfg)
    )
    got = np.asarray(tp_moe_mlp_op(
        x, wu_q, wd_q, ids, tw, mesh4, config=cfg,
        w_up_scale=us, w_down_scale=ds,
    ))
    denom = np.abs(want).max() + 1e-9
    assert np.abs(got - want).max() / denom < 4e-2

    # (c) straight-through backward on the int8 banks: runs, dx finite,
    # scale cotangents exactly zero (serving constants)
    def loss(x_, us_, ds_):
        return jnp.sum(tp_moe_mlp_grad(
            x_, wu_q, wd_q, ids, tw, "tp", jax.nn.gelu, cfg, None, True,
            us_, ds_,
        ) ** 2)

    g = jax.jit(_shard_map(
        jax.grad(loss, argnums=(0, 1, 2)), mesh1,
        (P("tp", None), P(None, None, None), P(None, None, None)),
        (P("tp", None), P(None, None, None), P(None, None, None)),
    ))
    dx, dus, dds = g(x, us, ds)
    assert np.isfinite(np.asarray(dx)).all() and np.abs(dx).max() > 0
    np.testing.assert_array_equal(np.asarray(dus), 0.0)
    np.testing.assert_array_equal(np.asarray(dds), 0.0)

    # (d) loud contracts
    with pytest.raises(ValueError, match="both"):
        tp_moe_mlp_op(x, wu_q, wd_q, ids, tw, mesh1, config=cfg,
                      w_up_scale=us)
    with pytest.raises(ValueError, match="int8"):
        tp_moe_mlp_op(x, w_up, w_down, ids, tw, mesh1, config=cfg,
                      w_up_scale=us, w_down_scale=ds)


@pytest.mark.parametrize("routing", ["topk1", "skewed"])
def test_tp_moe_overlap_edge_routing(mesh4, routing):
    """Edge routings for the fused pair: topk=1 (minimal expansion) and
    every-token-to-expert-0 (maximal per-rank padding: all but one
    expert's segments are sentinel blocks)."""
    from triton_dist_tpu.ops.grads import tp_moe_mlp_grad

    n, m_loc, n_exp, h_dim, f_dim = 4, 8, 3, 32, 64
    m_tot = n * m_loc
    topk = 1 if routing == "topk1" else 2
    cfg = GroupGemmConfig(block_m=4, block_n=32, block_k=32)
    kx, ku, kd = jax.random.split(jax.random.PRNGKey(29), 3)
    x = jax.random.normal(kx, (m_tot, h_dim), jnp.float32)
    w_up = jax.random.normal(ku, (n_exp, h_dim, f_dim)) / 8
    w_down = jax.random.normal(kd, (n_exp, f_dim, h_dim)) / 8
    if routing == "topk1":
        ids = jax.random.randint(
            jax.random.PRNGKey(30), (m_tot, 1), 0, n_exp, jnp.int32
        )
        tw = jnp.ones((m_tot, 1), jnp.float32)
    else:
        ids = jnp.zeros((m_tot, topk), jnp.int32)   # everything to expert 0
        tw = jnp.full((m_tot, topk), 0.5, jnp.float32)
    specs = (
        P("tp", None), P(None, None, "tp"), P(None, "tp", None),
        P("tp", None), P("tp", None),
    )

    def run(overlap):
        return np.asarray(jax.jit(
            jax.shard_map(
                lambda x, wu, wd, i, t: tp_moe_mlp_grad(
                    x, wu, wd, i, t, "tp", jax.nn.gelu, cfg, None, overlap
                ),
                mesh=mesh4, in_specs=specs, out_specs=P("tp", None),
                check_vma=False,
            )
        )(x, w_up, w_down, ids, tw))

    np.testing.assert_allclose(run(True), run(False), rtol=1e-5, atol=1e-5)
