"""fp8 end to end (ISSUE 19): the second OperandFormat (fp8_e4m3 expert
banks at quarter-rate weight bytes), the fp8 KV cache, and the fp8
kv_stream wire — plus the brownout3 rung that downshifts a serving
engine onto them under pressure.

Tier structure mirrors tests/test_serving.py:

- **host tier**: the three quantizers' round-trip/shape/byte contracts,
  the emitter identity pin (an fp8 capture is byte-identical to its w8
  twin — fp8 rides the w8 slot structure verbatim), perf-model
  quarter-rate honesty + the v4 no-fp8-path raise, the two-stage
  downshift ladder's config/controller arithmetic;
- **op tier** (CPU via guarded XLA fallbacks): grid ``group_gemm_fp8``
  and both fused overlap paths (through ``tp_moe_mlp_op`` world-1)
  against the dequantized golden, the fp8 kv_stream wire round-trip;
- **kernel tier** (``needs_interpreter`` / ``needs_dist`` — the same
  pre-existing seed gap markers as tests/test_emitter.py): fp8-KV
  decode/verify/paged parity incl. soft_cap and d=96, SP decode and
  ranged prefill over fp8 shards;
- **serving tier** (world-1 engine, FakeClock): brownout3 rebuilds AND
  reverts with zero lost requests and bit-identical replay, the
  armed-untriggered ≡ disarmed byte-identity pin, the fp8 handoff wire
  delivering through the corrupt-chunk guard ladder.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu import config as tdt_config
from triton_dist_tpu.models import init_params
from triton_dist_tpu.models.decode import Request
from triton_dist_tpu.models.tp_transformer import TransformerConfig
from triton_dist_tpu.ops.allgather_gemm import AGGemmConfig
from triton_dist_tpu.ops.gemm_reduce_scatter import GemmRSConfig
from triton_dist_tpu.ops.group_gemm import (
    FP8_DTYPE,
    GroupGemmConfig,
    group_gemm,
    group_gemm_fp8,
    quantize_expert_weights,
    quantize_expert_weights_fp8,
    resolve_w8,
)
from triton_dist_tpu.resilience import health, retry
from triton_dist_tpu.resilience.faults import FaultPlan
from triton_dist_tpu.serving import (
    Arrival,
    HandoffConfig,
    HandoffPlane,
    OverloadConfig,
    ServingConfig,
    ServingEngine,
    SLOTargets,
    TrafficSpec,
    generate_trace,
)
from triton_dist_tpu.serving import overload as ov




@pytest.fixture(autouse=True)
def _restore_config():
    cfg = tdt_config.get_config()
    snap = (cfg.fault_plan, cfg.elastic, cfg.suspect_threshold)
    yield
    tdt_config.update(
        fault_plan=snap[0], elastic=snap[1], suspect_threshold=snap[2]
    )
    retry.set_clock(None)


@pytest.fixture(scope="session")
def mesh1() -> Mesh:
    return Mesh(np.array(jax.devices()[:1]), ("tp",))


@pytest.fixture(scope="session")
def mesh2() -> Mesh:
    return Mesh(np.array(jax.devices()[:2]), ("tp",))


# ---------------------------------------------------------------------------
# Host tier: the three fp8 quantizers
# ---------------------------------------------------------------------------

def test_quantize_expert_weights_fp8_roundtrip():
    """The w8 quantizer's exact shape with 448 in 127's seat: fp8 bank +
    per-(expert, out-column) f32 scales, dequant within e4m3's 3-mantissa
    relative grid."""
    w = jax.random.normal(jax.random.PRNGKey(0), (3, 16, 8)) * 3.0
    wq, s = quantize_expert_weights_fp8(w)
    assert wq.dtype == FP8_DTYPE and wq.shape == w.shape
    assert s.shape == (3, 1, 8) and s.dtype == jnp.float32
    # same scale LAYOUT as int8 — every downstream scale-fold site is
    # shared between the two OperandFormats
    _, s_i8 = quantize_expert_weights(w)
    assert s.shape == s_i8.shape
    deq = np.asarray(wq.astype(jnp.float32) * s)
    err = np.abs(deq - np.asarray(w))
    # e4m3 keeps 3 mantissa bits: relative step 2^-4, plus the per-column
    # absmax quantum for the near-zero tail
    tol = np.abs(np.asarray(w)) * 0.0625 + np.abs(np.asarray(w)).max() / 448
    assert (err <= tol + 1e-6).all(), err.max()
    # quarter-rate byte contract vs the f32 bank (the whole point)
    assert wq.nbytes * 4 == w.astype(jnp.float32).nbytes


def test_quantize_kv_fp8_roundtrip_and_attention_golden():
    """fp8 KV cache: per-(batch, head, position) row scales in the int8
    family's ``[b, h, 1, s]`` layout; attention over the dequantized
    cache stays within quantization tolerance of the f32 reference —
    incl. the soft_cap posture and the non-pow-2 d=96 head dim."""
    from triton_dist_tpu.ops.flash_decode import FP8_KV_DTYPE, quantize_kv_fp8

    def deq(x_q, x_s):
        # scale rows [b, h, 1, s] broadcast back over the feature dim
        return x_q.astype(jnp.float32) * x_s[:, :, 0, :, None]

    for d in (32, 96):
        b, hq, h_kv, s = 2, 4, 2, 64
        q, k, v, kv_lens = _rand_case(
            jax.random.PRNGKey(10 + d), b, hq, h_kv, s, d
        )
        k_q, v_q, ks, vs = quantize_kv_fp8(k, v)
        assert k_q.dtype == FP8_KV_DTYPE and k_q.shape == k.shape
        assert ks.shape == (b, h_kv, 1, s) and ks.dtype == jnp.float32
        k_d, v_d = deq(k_q, ks), deq(v_q, vs)
        np.testing.assert_allclose(
            np.asarray(k_d), np.asarray(k), rtol=7e-2, atol=7e-2
        )
        for cap in (0.0, 15.0):
            got = _ref_decode_capped(q, k_d, v_d, kv_lens, soft_cap=cap)
            want = _ref_decode_capped(q, k, v, kv_lens, soft_cap=cap)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=6e-2, atol=6e-2
            )


def test_quantize_kv_wire_fp8_byte_accounting():
    """The fp8 wire's byte contract: the payload slab is a QUARTER of the
    f32 page bytes (one e4m3 byte per element), scales one f32 per row —
    the same wire shape as int8, dispatched by name."""
    from triton_dist_tpu.ops.kv_stream import (
        FP8_WIRE_DTYPE,
        dequantize_kv_wire,
        quantize_kv_wire_fp8,
        quantize_kv_wire_for,
    )

    pages = jax.random.normal(jax.random.PRNGKey(2), (8, 16), jnp.float32)
    q, s = quantize_kv_wire_fp8(pages)
    assert q.dtype == FP8_WIRE_DTYPE and q.shape == pages.shape
    assert s.shape == (8, 1) and s.dtype == jnp.float32
    assert q.nbytes * 4 == pages.nbytes
    deq = dequantize_kv_wire(q, s, pages.dtype)
    np.testing.assert_allclose(
        np.asarray(deq), np.asarray(pages), rtol=7e-2, atol=7e-2
    )
    # the by-name dispatch is the same function
    q2, s2 = quantize_kv_wire_for("fp8", pages)
    np.testing.assert_array_equal(np.asarray(q2), np.asarray(q))
    with pytest.raises(ValueError, match="quantized wire"):
        quantize_kv_wire_for("native", pages)


# ---------------------------------------------------------------------------
# Host tier: the emitter identity pin — fp8 rides the w8 slots verbatim
# ---------------------------------------------------------------------------

def test_fp8_capture_identical_to_w8_twin():
    """The tentpole's protocol claim, pinned: at the fp8 tune tuples'
    chunks=1 point the captured signal protocol is byte-identical to the
    w8 twin's (the operand format changes WHAT streams, never the
    slot/credit structure) and differs from bf16 only through the config
    label — world 1 has no comm kernel to capture and stays loud."""
    from triton_dist_tpu.analysis import sweep as S
    from triton_dist_tpu.analysis.capture import CaptureError
    from triton_dist_tpu.ops.allgather_group_gemm import (
        AG_GROUP_GEMM_TUNE_SPACE,
    )
    from triton_dist_tpu.ops.moe_reduce_rs import MOE_RS_TUNE_SPACE

    fams = (
        ("ag_group_gemm", AG_GROUP_GEMM_TUNE_SPACE),
        ("moe_reduce_rs", MOE_RS_TUNE_SPACE),
    )
    for fam, space in fams:
        fp8s = [
            c for c in space
            if getattr(c, "fp8", False) and c.chunks_per_shard == 1
        ]
        assert fp8s, f"{fam}: no chunks=1 fp8 tuple admitted"
        c = fp8s[0]
        w8_twin = dataclasses.replace(c, fp8=False, w8=True)
        bf16 = dataclasses.replace(c, fp8=False, w8=False)
        cap = S.capture_family(fam, 2, "pin", c).canonical()
        assert cap == S.capture_family(fam, 2, "pin", w8_twin).canonical()
        assert cap != S.capture_family(fam, 2, "pin", bf16).canonical()
    with pytest.raises(CaptureError, match="grid"):
        S.capture_family(
            "ag_group_gemm", 1, "w1",
            GroupGemmConfig(128, 1024, 512, fp8=True),
        )


# ---------------------------------------------------------------------------
# Op tier: fp8 grouped GEMM vs the dequantized golden (CPU-green via the
# guarded XLA fallbacks)
# ---------------------------------------------------------------------------

def test_group_gemm_fp8_matches_dequantized_golden():
    """Grid entry: ``(A @ B_q) · scale`` must equal the plain group_gemm
    over the DEQUANTIZED bank ``A @ (B_q · scale)`` — per-column scales
    commute with the contraction, so the only difference is f32 rounding
    order."""
    bm, K, N, E, nb = 8, 32, 16, 3, 6
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    a = jax.random.normal(k1, (nb * bm, K), jnp.float32)
    w = jax.random.normal(k2, (E, K, N)) / 4
    ids = jnp.array([0, 2, 1, 2, 0, 2], jnp.int32)
    wq, s = quantize_expert_weights_fp8(w)
    cfg = GroupGemmConfig(bm, N, K)
    got = group_gemm_fp8(a, wq, s, ids, config=cfg)
    want = group_gemm(a, wq.astype(jnp.float32) * s, ids, config=cfg)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5
    )


def test_tp_moe_fp8_fused_world1_and_loud_contracts(mesh1):
    """Both fused overlap paths (AG-GroupGEMM up, MoE-Reduce-RS down,
    composed by ``tp_moe_mlp_op``) under ``GroupGemmConfig(fp8=True)``:

    (a) world-1 on-the-fly quantize ≡ pre-quantized serving operands
    (same banks reach the GEMMs either way);
    (b) both within e4m3 weight-quantization tolerance of the f32 run;
    (c) the format contracts stay loud: w8+fp8 is unconstructible, a
    pre-quantized fp8 bank without its scales is rejected."""
    from triton_dist_tpu.ops.grads import tp_moe_mlp_op
    from triton_dist_tpu.ops.moe_utils import select_experts

    m_tok, h_dim, f_dim, n_exp, topk = 16, 32, 64, 3, 2
    kx, ku, kd, kl = jax.random.split(jax.random.PRNGKey(24), 4)
    x = jax.random.normal(kx, (m_tok, h_dim), jnp.float32)
    w_up = jax.random.normal(ku, (n_exp, h_dim, f_dim)) / 8
    w_down = jax.random.normal(kd, (n_exp, f_dim, h_dim)) / 8
    tw, ids = select_experts(
        jax.random.normal(kl, (m_tok, n_exp), jnp.float32), topk
    )
    cfg = GroupGemmConfig(4, 32, 32, fp8=True)
    wu_q, us = quantize_expert_weights_fp8(w_up)
    wd_q, ds = quantize_expert_weights_fp8(w_down)

    fly = tp_moe_mlp_op(x, w_up, w_down, ids, tw, mesh1, config=cfg)
    pre = tp_moe_mlp_op(
        x, wu_q, wd_q, ids, tw, mesh1, config=cfg,
        w_up_scale=us, w_down_scale=ds,
    )
    np.testing.assert_allclose(
        np.asarray(fly), np.asarray(pre), rtol=1e-4, atol=1e-6
    )
    want = np.asarray(tp_moe_mlp_op(
        x, w_up, w_down, ids, tw, mesh1,
        config=GroupGemmConfig(4, 32, 32),
    ))
    denom = np.abs(want).max() + 1e-9
    assert np.abs(np.asarray(pre) - want).max() / denom < 8e-2

    with pytest.raises(ValueError, match="exclusive"):
        GroupGemmConfig(4, 32, 32, w8=True, fp8=True)
    with pytest.raises(ValueError, match="scale"):
        resolve_w8(wu_q, None, cfg)


def test_quantize_moe_serving_params_fp8_format():
    """The serving-side bank quantizer's fmt axis: "fp8" produces e4m3
    pools with the int8 format's scale layout; an unknown format stays
    loud."""
    from triton_dist_tpu.models.tp_transformer import (
        quantize_moe_serving_params,
    )

    params = {
        "layers": [{
            "w_up": jax.random.normal(jax.random.PRNGKey(5), (2, 8, 16)),
            "w_down": jax.random.normal(jax.random.PRNGKey(6), (2, 16, 8)),
            "router": jnp.ones((8, 2)),
        }],
        "emb": jnp.ones((4, 4)),
    }
    out = quantize_moe_serving_params(params, fmt="fp8")
    layer = out["layers"][0]
    assert layer["w_up"].dtype == FP8_DTYPE
    assert layer["w_up_scale"].shape == (2, 1, 16)
    assert layer["w_down_scale"].shape == (2, 1, 8)
    # the int8 format's exact scale layout — downstream spec plumbing is
    # shared between the two serving formats
    i8 = quantize_moe_serving_params(params)["layers"][0]
    assert i8["w_up_scale"].shape == layer["w_up_scale"].shape
    # non-MoE leaves ride through untouched
    np.testing.assert_array_equal(
        np.asarray(out["emb"]), np.asarray(params["emb"])
    )
    np.testing.assert_array_equal(
        np.asarray(layer["router"]), np.asarray(params["layers"][0]["router"])
    )
    with pytest.raises(ValueError, match="fmt"):
        quantize_moe_serving_params(params, fmt="fp4")


# ---------------------------------------------------------------------------
# Op tier: the fp8 kv_stream wire (CPU-green via the XLA ppermute golden)
# ---------------------------------------------------------------------------

def test_kv_stream_op_fp8_wire_roundtrip(mesh2):
    """Mirror exchange on the fp8 wire: each PE's landed slab is exactly
    dequant(quant(mirror's slab)) — the wire cost is the quantization
    error and nothing else — and within e4m3 tolerance of the native
    wire's answer."""
    from triton_dist_tpu.ops.kv_stream import (
        KVStreamConfig,
        dequantize_kv_wire,
        kv_stream_op,
        quantize_kv_wire_fp8,
    )

    x = jax.random.normal(jax.random.PRNGKey(7), (8, 16), jnp.float32)
    got = kv_stream_op(
        x, mesh2, config=KVStreamConfig(chunks_per_shard=2, wire="fp8")
    )

    def rt(half):
        q, s = quantize_kv_wire_fp8(half)
        return dequantize_kv_wire(q, s, x.dtype)

    want = jnp.concatenate([rt(x[4:]), rt(x[:4])], axis=0)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6
    )
    native = kv_stream_op(x, mesh2, config=KVStreamConfig(wire="native"))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(native), rtol=7e-2, atol=7e-2
    )


def test_kv_stream_tune_space_has_fp8_wire_suffix():
    """Admission order on the wire axis too: every fp8-wire tuple sits
    strictly after all legacy (native/int8) tuples — append-only."""
    from triton_dist_tpu.ops.kv_stream import KV_STREAM_TUNE_SPACE

    wires = [c.wire for c in KV_STREAM_TUNE_SPACE]
    assert "fp8" in wires
    first_fp8 = wires.index("fp8")
    assert all(w == "fp8" for w in wires[first_fp8:])
    assert all(w != "fp8" for w in wires[:first_fp8])


# ---------------------------------------------------------------------------
# Kernel tier: fp8-KV decode/verify/paged parity (pre-existing seed gap
# markers — these cells run where the Mosaic interpreter / shard_map exist)
# ---------------------------------------------------------------------------

def _ref_decode_capped(q, k, v, kv_lens, soft_cap=0.0):
    """Pure-jnp masked attention golden with the optional tanh cap."""
    b, hq, d = q.shape
    _, h_kv, s, _ = k.shape
    g = hq // h_kv
    q4 = q.reshape(b, h_kv, g, d).astype(jnp.float32)
    scores = jnp.einsum("bhgd,bhsd->bhgs", q4, k.astype(jnp.float32))
    scores /= jnp.sqrt(jnp.float32(d))
    if soft_cap:
        scores = soft_cap * jnp.tanh(scores / soft_cap)
    mask = jnp.arange(s)[None, :] < kv_lens[:, None]
    scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bhsd->bhgd", p, v.astype(jnp.float32))
    return out.reshape(b, hq, d)


def _rand_case(key, b, hq, h_kv, s, d, dtype=jnp.float32):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    q = jax.random.normal(k1, (b, hq, d)).astype(dtype)
    k = jax.random.normal(k2, (b, h_kv, s, d)).astype(dtype)
    v = jax.random.normal(k3, (b, h_kv, s, d)).astype(dtype)
    kv_lens = jax.random.randint(k4, (b,), 1, s + 1, jnp.int32)
    return q, k, v, kv_lens


@pytest.mark.parametrize("soft_cap", [0.0, 20.0])
@pytest.mark.parametrize("d", [128, 96])
def test_flash_decode_fp8_parity(soft_cap, d):
    """fp8 KV cache decode kernel within quantization tolerance of the
    f32 reference — soft_cap and the non-pow-2 d=96 ride through exactly
    as on the int8 path."""
    from triton_dist_tpu.ops.flash_decode import (
        FlashDecodeConfig, flash_decode_fp8, quantize_kv_fp8,
    )

    b, hq, h_kv, s = 2, 4, 2, 64
    q, k, v, _ = _rand_case(jax.random.PRNGKey(30), b, hq, h_kv, s, d)
    kv_lens = jnp.array([s, 37], jnp.int32)
    cfg = FlashDecodeConfig(block_s=16, soft_cap=soft_cap)
    k_q, v_q, ks, vs = quantize_kv_fp8(k, v)
    got = flash_decode_fp8(q, k_q, v_q, ks, vs, kv_lens, config=cfg)
    want = _ref_decode_capped(q, k, v, kv_lens, soft_cap=soft_cap)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=8e-2, atol=8e-2
    )


def test_flash_verify_fp8_parity():
    """Multi-position verify over the fp8 cache: each verified position i
    attends its own prefix ``lens[:, i]`` — the ranged-verify contract;
    block_s=0 has no fp8 form and stays loud."""
    from triton_dist_tpu.ops.flash_decode import (
        FlashDecodeConfig, flash_verify_fp8, quantize_kv_fp8,
    )

    b, S, hq, h_kv, s, d = 2, 4, 4, 2, 64, 32
    _, k, v, _ = _rand_case(jax.random.PRNGKey(31), b, hq, h_kv, s, d)
    q = jax.random.normal(jax.random.PRNGKey(32), (b, S, hq, d), jnp.float32)
    lens = jnp.tile(jnp.arange(40, 40 + S, dtype=jnp.int32)[None], (b, 1))
    k_q, v_q, ks, vs = quantize_kv_fp8(k, v)
    got = flash_verify_fp8(
        q, k_q, v_q, ks, vs, lens,
        config=FlashDecodeConfig(block_s=16, soft_cap=15.0),
    )
    for i in range(S):
        want = _ref_decode_capped(q[:, i], k, v, lens[:, i], soft_cap=15.0)
        np.testing.assert_allclose(
            np.asarray(got[:, i]), np.asarray(want), rtol=8e-2, atol=8e-2
        )
    with pytest.raises(ValueError, match="fp8"):
        flash_verify_fp8(
            q, k_q, v_q, ks, vs, lens, config=FlashDecodeConfig(block_s=0)
        )


def test_paged_flash_decode_fp8_parity():
    """fp8 page pools (the paged × fp8 cell of the serving cache matrix):
    shuffled pages + block-table indirection, per-position scale pools."""
    from triton_dist_tpu.ops.flash_decode import (
        paged_flash_decode_fp8, quantize_kv_pages_fp8,
    )

    b, hq, h_kv, s, d, page = 3, 4, 2, 64, 32, 16
    q, k, v, _ = _rand_case(jax.random.PRNGKey(33), b, hq, h_kv, s, d)
    kv_lens = jnp.array([s, 25, 1], jnp.int32)
    kp, vp, bt = _paginate(k, v, page, key=jax.random.PRNGKey(34),
                           n_extra_pages=2)
    k_q, v_q, ks, vs = quantize_kv_pages_fp8(kp, vp)
    got = paged_flash_decode_fp8(q, k_q, v_q, ks, vs, kv_lens, bt)
    want = _ref_decode_capped(q, k, v, kv_lens)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=8e-2, atol=8e-2
    )


def _paginate(k, v, page_size, key=None, n_extra_pages=0):
    """Split a contiguous cache into shuffled pages + block table (the
    tests/test_flash_decode.py helper, restated)."""
    b, h_kv, s, d = k.shape
    ppseq = s // page_size
    n_pages = b * ppseq + n_extra_pages
    perm = (
        jax.random.permutation(key, n_pages)[: b * ppseq]
        if key is not None
        else jnp.arange(b * ppseq)
    )
    bt = perm.reshape(b, ppseq).astype(jnp.int32)
    kp = jnp.zeros((n_pages, h_kv, page_size, d), k.dtype)
    vp = jnp.zeros((n_pages, h_kv, page_size, d), v.dtype)
    k_chunks = k.reshape(b, h_kv, ppseq, page_size, d)
    v_chunks = v.reshape(b, h_kv, ppseq, page_size, d)
    for bi in range(b):
        for ci in range(ppseq):
            kp = kp.at[bt[bi, ci]].set(k_chunks[bi, :, ci])
            vp = vp.at[bt[bi, ci]].set(v_chunks[bi, :, ci])
    return kp, vp, bt


def test_flash_decode_fp8_distributed():
    """SP decode over a sequence-sharded fp8 cache merges to the f32
    distributed answer within quantization error (per-shard fp8 partials,
    standard (out ‖ lse) merge)."""
    from jax.sharding import PartitionSpec as P

    from triton_dist_tpu.ops.flash_decode import (
        FlashDecodeConfig, flash_decode_distributed,
        flash_decode_fp8_distributed, quantize_kv_fp8,
    )

    mesh4 = Mesh(np.array(jax.devices()[:4]), ("tp",))
    b, hq, h_kv, s, d = 2, 4, 2, 128, 32
    q, k, v, _ = _rand_case(jax.random.PRNGKey(35), b, hq, h_kv, s, d)
    kv_lens = jnp.array([s, 57], jnp.int32)
    s_loc = s // 4
    cfg = FlashDecodeConfig(block_s=8)

    def local_lens(me):
        return jnp.clip(kv_lens - me * s_loc, 0, s_loc)

    def f32_fn(q, k_s, v_s):
        me = jax.lax.axis_index("tp")
        return flash_decode_distributed(
            q, k_s, v_s, local_lens(me), axis="tp", config=cfg
        )

    def fp8_fn(q, k_s, v_s):
        me = jax.lax.axis_index("tp")
        k_q, v_q, ks, vs = quantize_kv_fp8(k_s, v_s)
        return flash_decode_fp8_distributed(
            q, k_q, v_q, ks, vs, local_lens(me), axis="tp", config=cfg
        )

    spec_kv = P(None, None, "tp", None)
    run = lambda fn: jax.jit(
        jax.shard_map(
            fn, mesh=mesh4, in_specs=(P(None, None, None), spec_kv, spec_kv),
            out_specs=P(None, None, None), check_vma=False,
        )
    )(q, k, v)
    want = run(f32_fn)
    got = run(fp8_fn)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=8e-2, atol=8e-2
    )


# ---------------------------------------------------------------------------
# Host tier: the perf model's quarter-rate weight term
# ---------------------------------------------------------------------------

def test_perf_model_fp8_quarter_rate_weight_term():
    """The honesty contract one rung down: fp8 QUARTERS exactly the
    weight-stream term w8 halves; the ring term never moves. Pricing fp8
    on a generation without an fp8 MXU path (v4) raises, and the two
    formats are mutually exclusive — the model must never return a time
    for hardware or a config that can't exist."""
    from triton_dist_tpu.perf_model import (
        CHIP_SPECS, estimate_w8_overlap_time_ms,
    )

    spec = CHIP_SPECS["v5e"]
    sb, n, wb = 1 << 20, 4, 1 << 26
    ring = estimate_w8_overlap_time_ms(sb, n, 0, spec=spec)
    full = estimate_w8_overlap_time_ms(sb, n, wb, spec=spec)
    w8 = estimate_w8_overlap_time_ms(sb, n, wb, w8=True, spec=spec)
    fp8 = estimate_w8_overlap_time_ms(sb, n, wb, fp8=True, spec=spec)
    assert full - ring == pytest.approx(2 * (w8 - ring))
    assert full - ring == pytest.approx(4 * (fp8 - ring))
    assert ring < fp8 < w8 < full
    with pytest.raises(ValueError, match="exclusive"):
        estimate_w8_overlap_time_ms(sb, n, wb, w8=True, fp8=True, spec=spec)
    with pytest.raises(ValueError, match="fp8"):
        estimate_w8_overlap_time_ms(sb, n, wb, fp8=True,
                                    spec=CHIP_SPECS["v4"])
    # every fp8-capable generation prices e4m3 at its int8 MXU rate; a 0
    # would make an fp8 roofline silently infinite (satellite 1's pin)
    for name in ("v5e", "v5p", "v6e"):
        assert CHIP_SPECS[name].fp8_tops == CHIP_SPECS[name].int8_tops
    assert CHIP_SPECS["v4"].fp8_tops == 0


# ---------------------------------------------------------------------------
# Host tier: the two-stage downshift ladder (brownout3)
# ---------------------------------------------------------------------------

def _stage(tag, seen):
    def stage(cfg):
        seen.append((tag, cfg))
        return cfg

    return stage


def test_overload_two_stage_ladder_config():
    """Single callable keeps the legacy 4-state ladder byte-identically;
    a 2-stage sequence grows it by the brownout3 rung; >2 stages and
    mis-sized pressure vectors stay loud."""
    assert OverloadConfig().ladder() == ov.LADDER
    one = OverloadConfig(downshift=lambda c: c).validate()
    assert one.ladder() == ov.LADDER
    assert len(one.downshift_stages()) == 1
    seen = []
    two = OverloadConfig(
        downshift=[_stage("w8", seen), _stage("fp8", seen)],
        enter_pressure=(0.5, 0.6, 0.7, 0.9),
        exit_pressure=(0.3, 0.4, 0.5, 0.7),
    ).validate()
    assert two.ladder() == (
        ov.NORMAL, ov.BROWNOUT1, ov.BROWNOUT2, ov.BROWNOUT3,
        ov.SHED_ALL_BATCH,
    )
    with pytest.raises(ValueError, match="at most 2"):
        OverloadConfig(
            downshift=[lambda c: c] * 3,
            enter_pressure=(0.5, 0.6, 0.7, 0.9),
            exit_pressure=(0.3, 0.4, 0.5, 0.7),
        ).validate()
    # two stages with the legacy 3-length pressures: the ladder has grown
    # a rung, so every rung must be named
    with pytest.raises(ValueError, match="rung"):
        OverloadConfig(downshift=[lambda c: c, lambda c: c]).validate()


def test_controller_walks_brownout3_and_back():
    """Unit ladder walk at the controller: climb through brownout3 into
    shed_all_batch (depth caps at the stage count), then descend peeling
    one stage per rung."""
    c = OverloadConfig(
        downshift=[lambda c: c, lambda c: c],
        enter_pressure=(0.2, 0.3, 0.4, 0.45),
        exit_pressure=(0.05, 0.1, 0.15, 0.2),
        min_dwell_steps=1, window_steps=4,
    )
    ctrl = ov.OverloadController(c, max_queue=10)
    depths = []
    for step in range(4):
        ctrl.observe_step(now=float(step), queue_depth=10)
        depths.append((ctrl.state, ctrl.downshift_depth()))
    assert depths == [
        (ov.BROWNOUT1, 0), (ov.BROWNOUT2, 1), (ov.BROWNOUT3, 2),
        (ov.SHED_ALL_BATCH, 2),  # shedding keeps the deepest composition
    ]
    for step in range(4, 8):
        ctrl.observe_step(now=float(step), queue_depth=0)
        depths.append((ctrl.state, ctrl.downshift_depth()))
    assert depths[4:] == [
        (ov.BROWNOUT3, 2), (ov.BROWNOUT2, 1), (ov.BROWNOUT1, 0),
        (ov.NORMAL, 0),
    ]


# ---------------------------------------------------------------------------
# Serving tier (world-1 engine, FakeClock): brownout3 end to end
# ---------------------------------------------------------------------------

def _tiny():
    cfg = TransformerConfig(
        vocab=32, hidden=32, ffn=64, n_layers=1, n_q_heads=4, n_kv_heads=2,
        head_dim=8, batch=2, seq=8,
        ag_config=AGGemmConfig(8, 16, 16), rs_config=GemmRSConfig(8, 16, 16),
    )
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def tiny1():
    return _tiny()


def _engine(tiny1, mesh1, *, clock=None, **serving_kw):
    cfg, params = tiny1
    clock = clock or retry.FakeClock()
    return ServingEngine(
        cfg, params, mesh1, s_max=16, clock=clock,
        serving=ServingConfig(virtual_step_s=0.01, **serving_kw),
    ), clock


@pytest.mark.chaos
def test_brownout3_rebuilds_and_reverts_bit_identical(tiny1, mesh1):
    """The brownout3 arc end to end: the crowd drives the 5-state ladder
    through BOTH precision rungs (each a counted rebuild through the
    elastic replay machinery), the sparse tail walks it back down, the
    base config is restored object-identically, no request is lost, and
    a fresh engine replays the same trace bit for bit."""

    def run():
        seen = []
        eng, clock = _engine(
            tiny1, mesh1, max_queue=4, slo=SLOTargets(ttft_ms=5.0),
            overload=OverloadConfig(
                min_dwell_steps=2, window_steps=4,
                downshift=[_stage("w8", seen), _stage("fp8", seen)],
                enter_pressure=(0.5, 0.6, 0.7, 0.8),
                exit_pressure=(0.3, 0.4, 0.5, 0.6),
            ),
        )
        crowd = [
            Arrival(t_s=0.0, request=Request([1, 2], max_new_tokens=4,
                                             uid=f"c{k}"))
            for k in range(8)
        ]
        tail = [
            Arrival(t_s=3.0 + k, request=Request([1, 2], max_new_tokens=1,
                                                 uid=f"t{k}"))
            for k in range(4)
        ]
        done = eng.serve(crowd + tail)
        return eng, seen, done

    eng, seen, done = run()
    rungs = {t.to for t in eng._overload.transitions}
    assert ov.BROWNOUT3 in rungs, eng._overload.transitions
    snap = eng.snapshot()
    # one counted downshift per deeper rung: brownout2 AND brownout3
    assert snap["requests"].get("precision_downshifts", 0) >= 2
    # stage 1 (the fp8 stage) really composed — and always on top of the
    # BASE config, never on an already-downshifted one
    assert [tag for tag, _ in seen].count("fp8") >= 1
    assert all(c is eng._base_cfg for tag, c in seen if tag == "w8")
    assert eng.cfg is eng._base_cfg, "precision restored on descent"
    assert eng.rebuilds >= 2
    reasons = [e.reason for e in health.events(health.SERVING_REBUILD)]
    assert any("downshift" in r for r in reasons)
    assert any("restored" in r for r in reasons)
    # zero lost requests: every uid reached a terminal Finished
    assert all(type(r).__name__ == "Finished" for r in done.values())
    # bit-identical replay: a fresh engine over the same trace
    _, _, done2 = run()
    assert {u: r.tokens for u, r in done.items()} == {
        u: r.tokens for u, r in done2.items()
    }


def test_brownout3_armed_untriggered_is_byte_identical(tiny1, mesh1):
    """The disarmed-by-default contract extended to the 5-state ladder:
    arming two downshift stages with unreachable thresholds serves every
    token stream byte-identically to the disarmed engine."""
    spec = TrafficSpec(rate_rps=20.0, n_requests=10, seed=11,
                      prompt_len=("uniform", 2, 4),
                      output_len=("uniform", 2, 5), vocab=32,
                      temperature=0.8)

    def run(overload):
        eng, _ = _engine(tiny1, mesh1, max_queue=64, overload=overload)
        done = eng.serve(generate_trace(spec))
        return {u: r.tokens for u, r in done.items()}

    armed = run(OverloadConfig(
        downshift=[lambda c: c, lambda c: c],
        enter_pressure=(0.97, 0.98, 0.99, 0.995),
        exit_pressure=(0.5, 0.6, 0.7, 0.8),
    ))
    disarmed = run(None)
    assert armed == disarmed


# ---------------------------------------------------------------------------
# Serving tier: the fp8 handoff wire
# ---------------------------------------------------------------------------

def _plane(**over):
    kw = dict(page_tokens=4, chunks_per_page=2)
    kw.update(over)
    return HandoffPlane(HandoffConfig(**kw), s_max=16, prefill_world=2,
                        decode_world=2)


def test_handoff_fp8_wire_config_and_delivery():
    """wire="fp8" validates, lowers to the fp8 member of the kv_stream
    tune space, and a transfer delivers with the wire recorded in the
    snapshot; a fantasy wire stays loud."""
    from triton_dist_tpu.ops.kv_stream import KV_STREAM_TUNE_SPACE

    cfg = HandoffConfig(page_tokens=4, chunks_per_page=2,
                        wire="fp8").validate()
    ks = cfg.kv_stream_config()
    assert ks.wire == "fp8" and ks in KV_STREAM_TUNE_SPACE
    p = _plane(wire="fp8")
    r = p.transfer("a", list(range(9)), now=0.0)
    assert r.outcome == "delivered" and r.pages_streamed == 3
    assert p.snapshot()["wire"] == "fp8"
    with pytest.raises(ValueError, match="wire"):
        HandoffConfig(wire="fp4").validate()


@pytest.mark.chaos
def test_handoff_fp8_wire_corrupt_chunk_chaos():
    """The guard ladder on the fp8 wire: one bounded bitflip mid-handoff
    re-sends in place (rung 1), the culprit decode PE is struck, the
    transfer still delivers — wire format changes the payload bytes, not
    the integrity protocol."""
    from triton_dist_tpu.resilience import elastic

    tdt_config.update(elastic=True, suspect_threshold=8)
    tdt_config.update(fault_plan=FaultPlan(
        "bitflip", pe=-1, pool="decode", max_triggers=1))
    try:
        p = _plane(wire="fp8")
        r = p.transfer("a", list(range(8)), now=0.0)
    finally:
        tdt_config.update(fault_plan=None, elastic=False)
    assert r.outcome == "delivered"
    assert r.retries == 1 and r.restreams == 0
    assert p.counters["canary_mismatches"] == 1
    assert r.culprit_pe in (2, 3)
    assert elastic.state(r.culprit_pe) == "suspect"
