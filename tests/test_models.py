"""Flagship TP transformer vs an unsharded jnp golden (forward parity,
vocab-parallel loss parity, gradient flow through the fused kernels)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # second tier: excluded from the quick CI tier
from jax.sharding import NamedSharding, PartitionSpec as P

from triton_dist_tpu.models import (
    TPTransformer,
    TransformerConfig,
    init_params,
    param_specs,
    train_step,
)
from triton_dist_tpu.models.tp_transformer import (
    _causal_gqa_attention,
    rmsnorm,
    rope,
    unpack_gate_up,
)
from triton_dist_tpu.ops.allgather_gemm import AGGemmConfig
from triton_dist_tpu.ops.gemm_reduce_scatter import GemmRSConfig


def _cfg(**kw):
    base = dict(
        vocab=64, hidden=32, ffn=64, n_layers=2, n_q_heads=8, n_kv_heads=4,
        head_dim=8, batch=2, seq=16,
        ag_config=AGGemmConfig(8, 16, 16), rs_config=GemmRSConfig(8, 16, 16),
    )
    base.update(kw)
    return TransformerConfig(**base)


def _ref_forward(tokens, params, cfg):
    """Unsharded pure-jnp forward with the same params/layout."""
    x = params["embed"][tokens.reshape(-1)]
    b, s = cfg.batch, cfg.seq
    g = cfg.n_q_heads // cfg.n_kv_heads
    d = cfg.head_dim
    for p in params["layers"]:
        h = rmsnorm(x, p["attn_norm"], cfg.norm_eps)
        # kv-group-major qkv layout (see init_params)
        qkv = (h @ p["wqkv"]).reshape(
            b, s, cfg.n_kv_heads, g + 2, d
        )
        q = qkv[..., :g, :].reshape(b, s, cfg.n_q_heads, d)
        k = qkv[..., g, :]
        v = qkv[..., g + 1, :]
        pos = jnp.arange(s, dtype=jnp.int32)
        q, k = rope(q, pos, cfg.rope_theta), rope(k, pos, cfg.rope_theta)
        attn = _causal_gqa_attention(q, k, v, cfg)
        x = x + attn.reshape(b * s, cfg.q_dim) @ p["wo"]
        h = rmsnorm(x, p["mlp_norm"], cfg.norm_eps)
        # two plain GEMMs over the unpacked halves: independent of how the
        # model pairs the stored columns
        gate, up = (h @ w for w in unpack_gate_up(p["w_gate_up"], cfg))
        x = x + (jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up) @ p["w_down"]
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x @ params["lm_head"]


def _ref_loss(tokens, targets, params, cfg):
    logits = _ref_forward(tokens, params, cfg).astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tl = jnp.take_along_axis(logits, targets[:, None], axis=1)[:, 0]
    return jnp.mean(lse - tl)


def _put_params(params, cfg, mesh):
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, param_specs(cfg),
    )


def test_tp_transformer_forward_parity(mesh4):
    cfg = _cfg()
    model = TPTransformer(cfg)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (cfg.batch * cfg.seq,), 0, cfg.vocab, jnp.int32
    )
    params_sh = _put_params(params, cfg, mesh4)
    got = jax.jit(
        jax.shard_map(
            lambda t, p: model(t, p), mesh=mesh4,
            in_specs=(P("tp"), param_specs(cfg)),
            out_specs=P(None, "tp"), check_vma=False,
        )
    )(tokens, params_sh)
    want = _ref_forward(tokens, params, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3)


def test_tp_transformer_loss_parity(mesh4):
    cfg = _cfg()
    model = TPTransformer(cfg)
    params = init_params(jax.random.PRNGKey(2), cfg)
    m = cfg.batch * cfg.seq
    tokens = jax.random.randint(jax.random.PRNGKey(3), (m,), 0, cfg.vocab, jnp.int32)
    targets = jax.random.randint(jax.random.PRNGKey(4), (m,), 0, cfg.vocab, jnp.int32)
    params_sh = _put_params(params, cfg, mesh4)
    got = jax.jit(
        jax.shard_map(
            lambda t, y, p: model.loss(t, y, p)[None], mesh=mesh4,
            in_specs=(P("tp"), P(None), param_specs(cfg)),
            out_specs=P("tp"), check_vma=False,
        )
    )(tokens, targets, params_sh)
    want = float(_ref_loss(tokens, targets, params, cfg))
    # every tp shard computes the identical full-batch loss
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


def test_tp_transformer_train_step_dp_tp(mesh2x4):
    """Full dp(2) x tp(4) training step: loss decreases and sharded/
    replicated grads are consistent with the unsharded reference step."""
    # 1 layer: the train-step property under test; 2-layer stacking stays
    # covered by the much cheaper forward/loss parity tests
    cfg = _cfg(n_layers=1)
    model = TPTransformer(cfg)
    params = init_params(jax.random.PRNGKey(5), cfg)
    m = cfg.batch * cfg.seq
    dp = 2
    tokens = jax.random.randint(jax.random.PRNGKey(6), (dp * m,), 0, cfg.vocab, jnp.int32)
    targets = jax.random.randint(jax.random.PRNGKey(7), (dp * m,), 0, cfg.vocab, jnp.int32)

    specs = param_specs(cfg)
    params_sh = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh2x4, s)), params, specs
    )

    def step(t, y, p):
        # t sharded over (dp, tp); y sharded over dp (replicated in tp)
        return train_step(model, p, t, y.reshape(-1), lr=1e-1)

    step_j = jax.jit(
        jax.shard_map(
            step, mesh=mesh2x4,
            in_specs=(P(("dp", "tp")), P("dp"), specs),
            out_specs=(specs, P()), check_vma=False,
        )
    )
    p1, loss1 = step_j(tokens, targets, params_sh)
    p2, loss2 = step_j(tokens, targets, p1)
    assert float(loss2) < float(loss1)

    # reference step on the dp=0 half must match the dp-mean direction only
    # loosely (different batch); instead check exact grad parity for one
    # replicated param via the unsharded loss on the full batch
    def full_loss(p):
        l = 0.0
        for i in range(dp):
            l = l + _ref_loss(
                tokens[i * m : (i + 1) * m], targets[i * m : (i + 1) * m], p, cfg
            )
        return l / dp

    g_ref = jax.grad(full_loss)(params)
    for name in ("final_norm", "embed"):  # replicated params: exact parity
        got_after = np.asarray(p1[name])
        want_after = np.asarray(params[name]) - 1e-1 * np.asarray(g_ref[name])
        np.testing.assert_allclose(
            got_after, want_after, rtol=2e-3, atol=2e-3, err_msg=name
        )


def _moe_ref_forward(tokens, params, cfg):
    """Dense per-token-expert golden forward (one MoE layer)."""
    from triton_dist_tpu.ops.moe_utils import select_experts

    m = tokens.shape[0]
    x = params["embed"][tokens]
    p = params["layers"][0]
    b, s, g, d = cfg.batch, cfg.seq, cfg.n_q_heads // cfg.n_kv_heads, cfg.head_dim
    h = rmsnorm(x, p["attn_norm"], cfg.norm_eps)
    qkv = (h @ p["wqkv"]).reshape(b, s, cfg.n_kv_heads, g + 2, d)
    q = qkv[..., :g, :].reshape(b, s, cfg.n_q_heads, d)
    k, v = qkv[..., g, :], qkv[..., g + 1, :]
    pos = jnp.arange(s, dtype=jnp.int32)
    q, k = rope(q, pos, cfg.rope_theta), rope(k, pos, cfg.rope_theta)
    attn = _causal_gqa_attention(q, k, v, cfg)
    x = x + attn.reshape(m, cfg.q_dim) @ p["wo"]
    h = rmsnorm(x, p["mlp_norm"], cfg.norm_eps)
    logits = h.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    tw, ids = select_experts(logits, cfg.topk)
    moe_out = np.zeros((m, cfg.hidden), np.float32)
    for t in range(m):
        for kk in range(cfg.topk):
            e = int(ids[t, kk])
            he = jax.nn.gelu(np.asarray(h)[t] @ np.asarray(p["w_up"])[e])
            moe_out[t] += float(tw[t, kk]) * (np.asarray(he) @ np.asarray(p["w_down"])[e])
    x = x + moe_out
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x @ params["lm_head"]


@pytest.mark.parametrize("kind", ["tp", "ep"])
def test_moe_transformer_forward_parity(mesh4, kind):
    """MoE decoder forward vs a dense per-token expert golden — the same
    answer whether experts are tensor-parallel (sliced over the FFN dim,
    AG-GroupGEMM/MoE-Reduce-RS) or expert-parallel (whole experts per PE,
    a2a dispatch/combine)."""
    from triton_dist_tpu.models import (
        EPMoETransformer, EPMoETransformerConfig, MoETransformerConfig,
        TPMoETransformer, ep_moe_param_specs, init_moe_params, moe_param_specs,
    )
    from triton_dist_tpu.ops.group_gemm import GroupGemmConfig
    from triton_dist_tpu.ops.moe_utils import select_experts

    shapes = dict(
        vocab=64, hidden=32, ffn=64, n_layers=1, n_q_heads=8, n_kv_heads=4,
        head_dim=8, batch=2, seq=16, n_experts=4, topk=2,
        ag_config=AGGemmConfig(8, 16, 16), rs_config=GemmRSConfig(8, 16, 16),
        gg_config=GroupGemmConfig(8, 16, 16),
    )
    if kind == "tp":
        cfg = MoETransformerConfig(**shapes)
        model, specs = TPMoETransformer(cfg), moe_param_specs(cfg)
    else:
        cfg = EPMoETransformerConfig(**shapes)
        model, specs = EPMoETransformer(cfg), ep_moe_param_specs(cfg)
    params = init_moe_params(jax.random.PRNGKey(8), cfg)
    m = cfg.batch * cfg.seq
    tokens = jax.random.randint(jax.random.PRNGKey(9), (m,), 0, cfg.vocab, jnp.int32)
    params_sh = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh4, s)), params, specs
    )
    got = jax.jit(
        jax.shard_map(
            lambda t, p: model(t, p), mesh=mesh4,
            in_specs=(P("tp"), specs), out_specs=P(None, "tp"), check_vma=False,
        )
    )(tokens, params_sh)

    want = _moe_ref_forward(tokens, params, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=5e-3, atol=5e-3)


def test_ep_moe_transformer_hier_forward(mesh2x4):
    """Hierarchical EP model wiring on a (dp, tp) mesh: attention TP over
    ``tp``, whole experts spread over all 8 PEs, two-phase dispatch over
    (dp, tp); each dp group runs its own token slice, so the golden is the
    dense forward per group."""
    from triton_dist_tpu.models import (
        EPMoETransformer, EPMoETransformerConfig, ep_moe_param_specs,
        init_moe_params,
    )
    from triton_dist_tpu.ops.group_gemm import GroupGemmConfig

    dp = 2
    cfg = EPMoETransformerConfig(
        vocab=64, hidden=32, ffn=64, n_layers=1, n_q_heads=8, n_kv_heads=4,
        head_dim=8, batch=2, seq=16, n_experts=8, topk=2, ep_outer="dp",
        ag_config=AGGemmConfig(8, 16, 16), rs_config=GemmRSConfig(8, 16, 16),
        gg_config=GroupGemmConfig(8, 16, 16),
    )
    model, specs = EPMoETransformer(cfg), ep_moe_param_specs(cfg)
    params = init_moe_params(jax.random.PRNGKey(10), cfg)
    m = cfg.batch * cfg.seq
    tokens = jax.random.randint(
        jax.random.PRNGKey(11), (dp * m,), 0, cfg.vocab, jnp.int32
    )
    params_sh = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh2x4, s)), params, specs
    )
    got = jax.jit(
        jax.shard_map(
            lambda t, p: model(t, p), mesh=mesh2x4,
            in_specs=(P(("dp", "tp")), specs),
            out_specs=P("dp", "tp"), check_vma=False,
        )
    )(tokens, params_sh)
    # drain the interpreted program before dispatching the eager golden:
    # concurrent io_callbacks + eager ops can starve XLA:CPU's thread pool
    # (the conftest deadlock note) on small-core hosts
    jax.block_until_ready(got)
    want = np.concatenate(
        [
            np.asarray(_moe_ref_forward(tokens[g * m : (g + 1) * m], params, cfg))
            for g in range(dp)
        ]
    )
    np.testing.assert_allclose(np.asarray(got), want, rtol=5e-3, atol=5e-3)


def test_models_package_imports():
    import triton_dist_tpu.models as m

    assert hasattr(m, "TPTransformer") and hasattr(m, "train_step")


def test_sp_transformer_forward_and_train(mesh4):
    """Context-parallel transformer: forward parity vs a full-sequence
    reference with the same (replicated) params; train step reduces loss."""
    from triton_dist_tpu.models.sp_transformer import (
        SPTransformer, SPTransformerConfig, sp_train_step,
    )
    from triton_dist_tpu.ops.ring_attention import RingAttentionConfig

    b, s = 1, 32
    cfg = SPTransformerConfig(
        vocab=32, hidden=32, ffn=64, n_layers=1, n_q_heads=2, n_kv_heads=1,
        head_dim=128, batch=b, seq=s,
        ring_config=RingAttentionConfig(block_q=8, block_kv=8),
    )
    model = SPTransformer(cfg)
    params = init_params(jax.random.PRNGKey(10), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(11), (b, s), 0, cfg.vocab, jnp.int32)
    targets = jax.random.randint(jax.random.PRNGKey(12), (b, s), 0, cfg.vocab, jnp.int32)

    got = jax.jit(
        jax.shard_map(
            lambda t, p: model(t, p), mesh=mesh4,
            in_specs=(P(None, "tp"), P(None)),
            out_specs=P(None, "tp", None), check_vma=False,
        )
    )(tokens, params)
    # reference: same weights through the dense _ref_forward (head-group
    # layout matches; MHA here via repeat inside the model)
    want = _ref_forward(tokens.reshape(-1), params, cfg).reshape(b, s, cfg.vocab)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-3, atol=3e-3)

    step = jax.jit(
        jax.shard_map(
            lambda t, y, p: sp_train_step(model, p, t, y, lr=5e-2),
            mesh=mesh4,
            in_specs=(P(None, "tp"), P(None, "tp"), P(None)),
            out_specs=(P(None), P()), check_vma=False,
        )
    )
    p1, l1 = step(tokens, targets, params)
    p2, l2 = step(tokens, targets, p1)
    assert float(l2) < float(l1)


def test_moe_transformer_train_step(mesh4):
    """MoE decoder trains end-to-end through the fused MoE kernels' custom
    VJP (router included): loss decreases over SGD steps."""
    from triton_dist_tpu.models import (
        MoETransformerConfig, TPMoETransformer, init_moe_params, moe_param_specs,
    )
    from triton_dist_tpu.ops.group_gemm import GroupGemmConfig

    cfg = MoETransformerConfig(
        vocab=64, hidden=32, ffn=64, n_layers=1, n_q_heads=8, n_kv_heads=4,
        head_dim=8, batch=2, seq=16, n_experts=4, topk=2,
        ag_config=AGGemmConfig(8, 16, 16), rs_config=GemmRSConfig(8, 16, 16),
        gg_config=GroupGemmConfig(8, 16, 16),
    )
    model = TPMoETransformer(cfg)
    specs = moe_param_specs(cfg)
    params = init_moe_params(jax.random.PRNGKey(20), cfg)
    m = cfg.batch * cfg.seq
    tokens = jax.random.randint(jax.random.PRNGKey(21), (m,), 0, cfg.vocab, jnp.int32)
    targets = jax.random.randint(jax.random.PRNGKey(22), (m,), 0, cfg.vocab, jnp.int32)
    params_sh = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh4, s)), params, specs
    )
    step = jax.jit(
        jax.shard_map(
            lambda t, y, p: train_step(model, p, t, y, lr=1e-1, dp_axis=None),
            mesh=mesh4, in_specs=(P("tp"), P(None), specs),
            out_specs=(specs, P()), check_vma=False,
        )
    )
    p1, loss1 = step(tokens, targets, params_sh)
    jax.block_until_ready(loss1)
    p2, loss2 = step(tokens, targets, p1)
    jax.block_until_ready(loss2)
    p3, loss3 = step(tokens, targets, p2)
    assert float(loss2) < float(loss1)
    assert float(loss3) < float(loss2)
    # router actually moved (its grad flows through the routing weights)
    r0 = np.asarray(params["layers"][0]["router"])
    r1 = np.asarray(p1["layers"][0]["router"])
    assert np.abs(r1 - r0).max() > 0


def test_ep_moe_transformer_train_step(mesh4):
    """Flat expert-parallel MoE decoder trains end-to-end (a2a + grouped
    GEMM VJPs compose): loss decreases, router moves."""
    from triton_dist_tpu.models import (
        EPMoETransformer, EPMoETransformerConfig, ep_moe_param_specs,
        init_moe_params,
    )
    from triton_dist_tpu.ops.group_gemm import GroupGemmConfig

    cfg = EPMoETransformerConfig(
        vocab=64, hidden=32, ffn=64, n_layers=1, n_q_heads=8, n_kv_heads=4,
        head_dim=8, batch=2, seq=16, n_experts=4, topk=2,
        ag_config=AGGemmConfig(8, 16, 16), rs_config=GemmRSConfig(8, 16, 16),
        gg_config=GroupGemmConfig(8, 16, 16),
    )
    model, specs = EPMoETransformer(cfg), ep_moe_param_specs(cfg)
    params = init_moe_params(jax.random.PRNGKey(30), cfg)
    m = cfg.batch * cfg.seq
    tokens = jax.random.randint(jax.random.PRNGKey(31), (m,), 0, cfg.vocab, jnp.int32)
    targets = jax.random.randint(jax.random.PRNGKey(32), (m,), 0, cfg.vocab, jnp.int32)
    params_sh = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh4, s)), params, specs
    )
    step = jax.jit(
        jax.shard_map(
            lambda t, y, p: train_step(model, p, t, y, lr=1e-1, dp_axis=None),
            mesh=mesh4, in_specs=(P("tp"), P(None), specs),
            out_specs=(specs, P()), check_vma=False,
        )
    )
    p1, loss1 = step(tokens, targets, params_sh)
    jax.block_until_ready(loss1)
    p2, loss2 = step(tokens, targets, p1)
    jax.block_until_ready(loss2)
    assert float(loss2) < float(loss1)
    r0 = np.asarray(params["layers"][0]["router"])
    r1 = np.asarray(p1["layers"][0]["router"])
    assert np.abs(r1 - r0).max() > 0


def test_train_step_rejects_ep_quant():
    """ep_quant is inference-only (the quantized wire zeroes the router
    gradient — test_quant_dispatch_grad_is_zero); train_step must refuse
    it loudly rather than train a dead router silently."""
    import pytest

    from triton_dist_tpu.models import EPMoETransformer, EPMoETransformerConfig

    cfg = EPMoETransformerConfig(
        vocab=64, hidden=32, ffn=64, n_layers=1, n_q_heads=8, n_kv_heads=4,
        head_dim=8, batch=2, seq=16, n_experts=4, topk=2, ep_quant="int8",
    )
    model = EPMoETransformer(cfg)
    with pytest.raises(ValueError, match="ep_quant"):
        train_step(model, {}, None, None)


def _moe_dense_forward(tokens, params, cfg):
    """Differentiable dense golden forward for the (1-layer) MoE decoder
    (einsum MoE instead of _moe_ref_forward's numpy loop)."""
    from triton_dist_tpu.ops.moe_utils import select_experts

    m = tokens.shape[0]
    x = params["embed"][tokens]
    p = params["layers"][0]
    b, s, g, d = cfg.batch, cfg.seq, cfg.n_q_heads // cfg.n_kv_heads, cfg.head_dim
    h = rmsnorm(x, p["attn_norm"], cfg.norm_eps)
    qkv = (h @ p["wqkv"]).reshape(b, s, cfg.n_kv_heads, g + 2, d)
    q = qkv[..., :g, :].reshape(b, s, cfg.n_q_heads, d)
    k, v = qkv[..., g, :], qkv[..., g + 1, :]
    pos = jnp.arange(s, dtype=jnp.int32)
    q, k = rope(q, pos, cfg.rope_theta), rope(k, pos, cfg.rope_theta)
    attn = _causal_gqa_attention(q, k, v, cfg)
    x = x + attn.reshape(m, cfg.q_dim) @ p["wo"]
    h = rmsnorm(x, p["mlp_norm"], cfg.norm_eps)
    logits = h.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    tw, ids = select_experts(logits, cfg.topk)
    he = jax.nn.gelu(jnp.einsum("th,tkhf->tkf", h, p["w_up"][ids]))
    y = jnp.einsum("tkf,tkfh->tkh", he, p["w_down"][ids])
    x = x + jnp.sum(tw.astype(jnp.float32)[:, :, None] * y, axis=1)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x @ params["lm_head"]


def test_ep_moe_transformer_hier_train_grad_parity(mesh2x4):
    """The dp x tp hierarchical EP training step applies the EXACT gradient
    of the dp-mean loss — in particular the dp-sharded expert banks must
    NOT be pmean'd across dp ranks holding different experts."""
    from triton_dist_tpu.models import (
        EPMoETransformer, EPMoETransformerConfig, ep_moe_param_specs,
        init_moe_params,
    )
    from triton_dist_tpu.ops.group_gemm import GroupGemmConfig

    dp, lr = 2, 1e-1
    cfg = EPMoETransformerConfig(
        vocab=64, hidden=32, ffn=64, n_layers=1, n_q_heads=8, n_kv_heads=4,
        head_dim=8, batch=2, seq=16, n_experts=8, topk=2, ep_outer="dp",
        ag_config=AGGemmConfig(8, 16, 16), rs_config=GemmRSConfig(8, 16, 16),
        gg_config=GroupGemmConfig(8, 16, 16),
    )
    model, specs = EPMoETransformer(cfg), ep_moe_param_specs(cfg)
    params = init_moe_params(jax.random.PRNGKey(40), cfg)
    m = cfg.batch * cfg.seq
    tokens = jax.random.randint(
        jax.random.PRNGKey(41), (dp * m,), 0, cfg.vocab, jnp.int32
    )
    targets = jax.random.randint(
        jax.random.PRNGKey(42), (dp * m,), 0, cfg.vocab, jnp.int32
    )
    params_sh = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh2x4, s)), params, specs
    )
    p1, _ = jax.jit(
        jax.shard_map(
            lambda t, y, p: train_step(model, p, t, y.reshape(-1), lr=lr),
            mesh=mesh2x4, in_specs=(P(("dp", "tp")), P("dp"), specs),
            out_specs=(specs, P()), check_vma=False,
        )
    )(tokens, targets, params_sh)
    jax.block_until_ready(p1)

    def dense_ce(toks, tgts, p):
        logits = _moe_dense_forward(toks, p, cfg).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tl = jnp.take_along_axis(logits, tgts[:, None], axis=1)[:, 0]
        return jnp.mean(lse - tl)

    def full_loss(p):
        l = 0.0
        for i in range(dp):
            l = l + dense_ce(
                tokens[i * m : (i + 1) * m], targets[i * m : (i + 1) * m], p
            )
        return l / dp

    g_ref = jax.grad(full_loss)(params)
    for name, got, want_p, want_g in (
        ("w_up", p1["layers"][0]["w_up"], params["layers"][0]["w_up"],
         g_ref["layers"][0]["w_up"]),
        ("w_down", p1["layers"][0]["w_down"], params["layers"][0]["w_down"],
         g_ref["layers"][0]["w_down"]),
        ("router", p1["layers"][0]["router"], params["layers"][0]["router"],
         g_ref["layers"][0]["router"]),
        ("embed", p1["embed"], params["embed"], g_ref["embed"]),
    ):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want_p) - lr * np.asarray(want_g),
            rtol=2e-3, atol=2e-3, err_msg=name,
        )


def test_sp_transformer_zigzag_matches_contig(mesh4):
    """Zigzag SP transformer on permuted tokens produces exactly the
    contiguous model's logits (unpermuted) — same math, balanced causal
    load."""
    from triton_dist_tpu.models.sp_transformer import (
        SPTransformer, SPTransformerConfig,
    )
    from triton_dist_tpu.ops.ring_attention import (
        RingAttentionConfig, zigzag_permutation,
    )

    b, s, n = 1, 32, 4
    base = dict(
        vocab=32, hidden=32, ffn=64, n_layers=1, n_q_heads=2, n_kv_heads=1,
        head_dim=128, batch=b, seq=s,
        ring_config=RingAttentionConfig(block_q=4, block_kv=4),
    )
    params = init_params(jax.random.PRNGKey(50), SPTransformerConfig(**base))
    tokens = jax.random.randint(jax.random.PRNGKey(51), (b, s), 0, 32, jnp.int32)

    def run(model, toks):
        return jax.jit(
            jax.shard_map(
                lambda t, p: model(t, p), mesh=mesh4,
                in_specs=(P(None, "tp"), P(None)),
                out_specs=P(None, "tp", None), check_vma=False,
            )
        )(toks, params)

    want = run(SPTransformer(SPTransformerConfig(**base)), tokens)
    jax.block_until_ready(want)
    perm, inv = zigzag_permutation(n, s)
    got_z = run(
        SPTransformer(SPTransformerConfig(**base, zigzag=True)),
        tokens[:, perm],
    )
    jax.block_until_ready(got_z)
    np.testing.assert_allclose(
        np.asarray(got_z)[:, inv], np.asarray(want), rtol=2e-4, atol=2e-4
    )


def test_train_step_with_optax_adam(mesh4):
    """train_step takes any optax transform: adam state shards via
    opt_state_specs (param-mirroring subtrees get the param specs, counts
    replicate) and the loss decreases."""
    import optax

    from triton_dist_tpu.models import opt_state_specs

    cfg = _cfg(n_layers=1)  # optimizer plumbing, not model depth
    model = TPTransformer(cfg)
    params = init_params(jax.random.PRNGKey(60), cfg)
    m = cfg.batch * cfg.seq
    tokens = jax.random.randint(jax.random.PRNGKey(61), (m,), 0, cfg.vocab, jnp.int32)
    targets = jax.random.randint(jax.random.PRNGKey(62), (m,), 0, cfg.vocab, jnp.int32)
    opt = optax.adam(1e-2)
    specs = param_specs(cfg)
    o_specs = opt_state_specs(opt, params, specs)
    params_sh = _put_params(params, cfg, mesh4)
    opt_state = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh4, s)),
        opt.init(params), o_specs,
    )
    step = jax.jit(
        jax.shard_map(
            lambda t, y, p, o: train_step(
                model, p, t, y, dp_axis=None, opt=opt, opt_state=o
            ),
            mesh=mesh4, in_specs=(P("tp"), P(None), specs, o_specs),
            out_specs=(specs, o_specs, P()), check_vma=False,
        )
    )
    p1, o1, loss1 = step(tokens, targets, params_sh, opt_state)
    jax.block_until_ready(loss1)
    p2, o2, loss2 = step(tokens, targets, p1, o1)
    jax.block_until_ready(loss2)
    assert float(loss2) < float(loss1)


def test_ep_moe_transformer_quantized_forward(mesh2x4):
    """EP-MoE forward with serving-quantized expert banks (int8 pools +
    scales, EP expert-dim sharding): logits within weight-quant tolerance
    of the full-precision model — the scales route through EPMoEMLP's
    scale-folding grouped GEMM."""
    from triton_dist_tpu.models import (
        EPMoETransformer, EPMoETransformerConfig, init_moe_params,
        quantize_moe_serving_params, specs_for,
    )
    from triton_dist_tpu.ops.group_gemm import GroupGemmConfig

    cfg = EPMoETransformerConfig(
        vocab=64, hidden=32, ffn=64, n_layers=1, n_q_heads=8, n_kv_heads=4,
        head_dim=8, batch=2, seq=16, n_experts=8, topk=2, ep_outer="dp",
        ag_config=AGGemmConfig(8, 16, 16), rs_config=GemmRSConfig(8, 16, 16),
        gg_config=GroupGemmConfig(8, 16, 16),
    )
    model = EPMoETransformer(cfg)
    params = init_moe_params(jax.random.PRNGKey(90), cfg)
    q_params = quantize_moe_serving_params(params)
    dp, m = 2, cfg.batch * cfg.seq
    tokens = jax.random.randint(
        jax.random.PRNGKey(91), (dp * m,), 0, cfg.vocab, jnp.int32
    )

    def logits_of(p):
        sp = specs_for(cfg, p)
        p_sh = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh2x4, s)), p, sp
        )
        out = jax.jit(
            jax.shard_map(
                lambda t, pp: model(t, pp), mesh=mesh2x4,
                in_specs=(P(("dp", "tp")), sp),
                out_specs=P("dp", "tp"), check_vma=False,
            )
        )(tokens, p_sh)
        jax.block_until_ready(out)
        return out

    lf = np.asarray(logits_of(params), np.float32)
    lq = np.asarray(logits_of(q_params), np.float32)
    np.testing.assert_allclose(lq, lf, rtol=3e-2, atol=3e-2 * np.abs(lf).max())
