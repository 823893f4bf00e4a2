"""The window-attention / gated-expert family (models/window_moe.py) at
toy widths on the CPU (window 8, page 4, so a ring of 3 pages; 8 experts,
top-2), each piece against the plain reference's equations
(perfbench/references/exaone_window_moe.py, imported as it stands: it
shares no code with the program). Weights are float32 here, so the
tolerances below are those of float32 arithmetic reordered (banded vs
masked attention, online vs whole softmax, grouped vs dense expert sums),
not of bf16: the lower-precision control is far outside them (last test
but one)."""

import dataclasses
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu.models import ContinuousBatcher, Request
from triton_dist_tpu.models import gated_experts, window_moe
from triton_dist_tpu.models.decode import (
    PAGED_CACHE_KINDS, WindowPagedKVCacheSpec,
)
from triton_dist_tpu.models.tp_transformer import _causal_gqa_attention

PERFBENCH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "perfbench")
if PERFBENCH not in sys.path:
    sys.path.insert(0, PERFBENCH)
from harness import cells, correct  # noqa: E402

from admission_helpers import (  # noqa: E402
    admission_kernel_operands, check_admission,
)
from scope_helpers import check_pass  # noqa: E402

# (the package exports a function under the module's name)
fd = importlib.import_module("triton_dist_tpu.ops.flash_decode")

# float32 everywhere: what is left is the order of the sums
TOL = dict(rtol=2e-4, atol=2e-4)
GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "paged_flash_decode_dense.jaxpr.txt")

WINDOW, PAGE, S_MAX = 8, 4, 32
KINDS = ["sliding_attention", "sliding_attention", "full_attention",
         "sliding_attention"]
TOY = dict(
    hidden=64, ffn=128, n_layers=4, n_q_heads=4, n_kv_heads=2, head_dim=8,
    vocab=128, rope_theta=10000.0, norm_eps=1e-5, dtype="float32",
    layer_types=KINDS, sliding_windows=[WINDOW, WINDOW, 0, WINDOW],
    sliding_window=WINDOW, num_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=32, num_shared_experts=1, first_k_dense_replace=1,
    routed_scaling_factor=2.5, scoring_func="sigmoid", n_group=1,
    topk_group=1, norm_topk_prob=True,
    engine=dict(slots=2, s_max=S_MAX, page=PAGE, max_queue=64),
)
TOY["sizes"] = {k: TOY[k] for k in cells.SIZE_KEYS}
SIZES = TOY["sizes"]


@pytest.fixture(scope="module")
def ref():
    mod = cells.load_module("references", "exaone_window_moe")
    mod.configure(TOY)
    yield mod
    mod.configure(TOY)


@pytest.fixture(scope="module")
def adapter():
    return cells.load_module("programs", "tdt_window_moe")


@pytest.fixture(scope="module")
def toy(ref, adapter):
    """``(cfg, program params, plain layers, outer)`` from one seed."""
    cfg = adapter.model_config(TOY)
    key = ref.seed_key(7)
    plain = [ref.layer_weights(key, li, SIZES) for li in range(TOY["n_layers"])]
    outer = ref.outer_weights(key, SIZES)
    params = dict(outer, layers=[adapter.pack_layer(w, cfg) for w in plain])
    return cfg, params, plain, outer


def _ref_logits(ref, plain, outer, tokens, control=False):
    """The reference's logits at every position of ``tokens [n, T]``."""
    x = outer["embed"][tokens].astype(jnp.float32)
    for li, w in enumerate(plain):
        x = ref.layer(x, w, SIZES, li, control)
    n, t = tokens.shape
    return np.asarray(ref.head(x, outer, jnp.zeros(n, jnp.int32), t, SIZES,
                               control))


def _mesh(cfg):
    return Mesh(np.array(jax.devices()[:1]), (cfg.axis,))


def test_the_plan_names_attention_and_mlp_and_the_cache_has_two_lifetimes(toy):
    cfg, params, _, _ = toy
    assert window_moe.layer_plan(cfg) == (
        ("window", "dense"), ("window", "moe"), ("full", "moe"),
        ("window", "moe"))
    assert (cfg.own_passes, cfg.cache_kind) == (True, "kv_window")
    assert PAGED_CACHE_KINDS["kv_window"] is WindowPagedKVCacheSpec
    assert {"kv", "kv_window", "latent"} <= set(PAGED_CACHE_KINDS)
    from triton_dist_tpu.models.tp_transformer import specs_for

    specs = specs_for(cfg)
    assert jax.tree.structure(jax.tree.map(lambda x: 0, params)) == \
        jax.tree.structure(jax.tree.map(
            lambda s: 0, specs, is_leaf=lambda s: not isinstance(s, (dict, list))))
    init = window_moe.init_window_moe_params(jax.random.PRNGKey(0), cfg)
    assert jax.tree.map(jnp.shape, init) == jax.tree.map(jnp.shape, params)
    assert gated_experts.expert_bytes(params) == 3 * 8 * 3 * 64 * 32 * 4
    spec = WindowPagedKVCacheSpec(S_MAX, PAGE, static_table=True)
    assert spec.ring(cfg) == 3                 # ceil(8 / 4) + 1
    cache = spec.init(cfg, 1)
    # one full layer over s_max / page pages a slot, three window layers
    # over the ring
    assert cache["k_full"].shape == (1, 2 * 8, 2, PAGE, 8)
    assert cache["k_win"].shape == (3, 2 * 3, 2, PAGE, 8)
    assert cache["block_table"].shape == (1, 2, 8)
    assert cache["block_table_win"].shape == (1, 2, 3)
    assert set(spec.specs(cfg)) == set(cache)
    # a window as long as the sequence needs no more than the sequence
    assert spec.ring(dataclasses.replace(cfg, window=S_MAX)) == S_MAX // PAGE


@pytest.mark.parametrize("length", [5, 8, 19])
def test_full_forward_matches_reference(toy, ref, length):
    """The program's forward (banded window layers, grouped GEMMs) at
    lengths below, at and past the window, a whole number of blocks or
    not."""
    cfg, params, plain, outer = toy
    tokens = jax.random.randint(jax.random.PRNGKey(length), (2, length), 0,
                                cfg.vocab)
    got = window_moe.forward_logits(cfg, params, tokens)
    np.testing.assert_allclose(
        np.asarray(got), _ref_logits(ref, plain, outer, tokens), **TOL)


class _Recording(Request):
    """A request that keeps every logit row it was sampled from and then
    takes the best token: logits are compared, not tokens."""

    def sample(self, logits, rng):
        self.__dict__.setdefault("rows", []).append(np.array(logits))
        return int(np.argmax(logits))


# (prompt, new): contexts below the window throughout; ending AT the
# window; a prompt shorter than the window decoding past it and across a
# ring wrap (12 positions); a prompt longer than the ring, whose prefill
# wraps, then two more wraps of decoding; a slot re-admitted mid-run
CASES = {"below": (3, 4), "at": (5, 3), "past": (6, 12), "wrapped": (14, 14),
         "readmitted": (9, 6)}


@pytest.fixture(scope="module")
def served(toy):
    """Every case through ONE batcher (2 slots, so slots are re-used):
    prefill into the two kinds of pool, then decode steps through the
    window and the full kernel, ragged positions."""
    cfg, params, _, _ = toy
    batcher = ContinuousBatcher(
        cfg, params, _mesh(cfg), s_max=S_MAX, page_size=PAGE, prefill=True)
    assert isinstance(batcher.spec, WindowPagedKVCacheSpec)
    rng = np.random.default_rng(0)
    reqs = {
        name: _Recording(list(rng.integers(0, cfg.vocab, n_prompt)), n_new,
                         temperature=1.0, uid=name)
        for name, (n_prompt, n_new) in CASES.items()}
    for r in reqs.values():
        batcher.submit(r)
    return reqs, dict(batcher.run())


@pytest.mark.parametrize("case", sorted(CASES))
def test_batcher_prefill_then_decode_matches_reference(toy, ref, served, case):
    """Every logit row the batcher sampled from against the reference's
    full forward over the same sequence."""
    _, _, plain, outer = toy
    reqs, done = served
    r, out = reqs[case], done[case]
    assert len(out) == r.max_new_tokens == len(r.rows)
    seq = np.array([list(r.prompt) + out])
    want = _ref_logits(ref, plain, outer, seq)[0]
    first = len(r.prompt) - 1
    np.testing.assert_allclose(
        np.stack(r.rows), want[first:first + len(out)], **TOL)


POOLS = {"k_full": "block_table", "v_full": "block_table",
         "k_win": "block_table_win", "v_win": "block_table_win"}


@pytest.mark.parametrize("length,bucket", [(5, 8), (14, 16)])
@pytest.mark.parametrize("slot", [0, -1])
def test_an_admission_runs_and_writes_the_admitted_slot_only(
        toy, slot, length, bucket):
    """A one-hot mask on the first or the last slot, a prompt shorter than
    its bucket and one longer than the ring (12 positions, so its window
    layers' rows wrap): the other slot's pages and rings bit-identical,
    the admitted slot's rows and logit row the unmasked whole-batch
    pass's, and one slot's rows counted."""
    cfg, params, _, _ = toy
    spec = WindowPagedKVCacheSpec(S_MAX, PAGE, static_table=True)
    assert 5 < spec.ring(cfg) * PAGE < 14
    counters = check_admission(
        cfg, params, spec, S_MAX, POOLS, slot % cfg.batch, length, bucket,
        n_moe=3, tol=TOL, seed=bucket + slot)
    # every expert is held here; an admission reads no key rows
    assert [int(v) for v in counters[len(gated_experts.MOE_STATS):]] == [0, 0, 0]


def test_the_lowered_admission_does_not_grow_with_the_batch(toy):
    """The grouped GEMMs of an admission read the same operands at 2 slots
    and at 4: one slot's ``bucket x topk`` assignments, aligned."""
    cfg, params, _, _ = toy
    spec = WindowPagedKVCacheSpec(S_MAX, PAGE, static_table=True)
    two, four = (admission_kernel_operands(cfg, params, spec, S_MAX, 32, b)
                 for b in (2, 4))
    assert len(two) == 2 * 3 and two == four        # 2 GEMMs x 3 expert layers
    # the sorted rows: 32 x top-2 assignments, each of the 8 experts padded
    # to a 128-row block (64 + 8 x 127, rounded up = 1152), whatever the
    # batch (4 slots' rows would be 1280), walked a chunk of one block at a
    # time (gated_experts._chunk_blocks at expert_ffn 32): a chunk's rows
    # in, and the whole result the down GEMM writes into
    assert {s[0] for call in two for s in call if len(s) == 2} == {128, 1152}


# the family's row of the table of scopes (docs/observability.md): window
# and full layers are both ``attn``; the toy plan has a dense layer and
# three expert layers, each with a shared expert
SCOPES = {"attn", "attn/qkv", "attn/kv_write", "attn/out",
          "ffn", "ffn/gate_up", "ffn/act", "ffn/down", "ffn/route",
          "ffn/experts", "ffn/shared", "head"}


@pytest.mark.parametrize("which", ["step", "admission"])
def test_every_part_of_a_pass_says_which_part_it_is(toy, which):
    """The lowered step and admission carry every scope of the family's
    row and no other ``tdt.`` name, and every matrix product and kernel
    call lies under a part; only the step calls the decode kernel."""
    cfg, params, _, _ = toy
    spec = WindowPagedKVCacheSpec(S_MAX, PAGE, static_table=True)
    check_pass(which, cfg, params, spec, _mesh(cfg), S_MAX, SCOPES)


def _ring_pools(rng, b, h_kv, d, lens, ring):
    """Contiguous k, v ``[b, h_kv, S_MAX, d]`` and the same rows as a ring
    would hold them after ``lens`` positions (older rows overwritten)."""
    k = rng.standard_normal((b, h_kv, S_MAX, d)).astype(np.float32)
    v = rng.standard_normal((b, h_kv, S_MAX, d)).astype(np.float32)
    table = (np.arange(b)[:, None] * ring + np.arange(ring)[None, :]).astype(np.int32)
    # the pool's pages in another order than the table's
    table = (table * 5) % (b * ring) if np.gcd(5, b * ring) == 1 else table
    kp = np.zeros((b * ring, h_kv, PAGE, d), np.float32)
    vp = np.zeros_like(kp)
    for i in range(b):
        for p in range(lens[i]):
            page = table[i, (p // PAGE) % ring]
            kp[page, :, p % PAGE], vp[page, :, p % PAGE] = k[i, :, p], v[i, :, p]
    return k, v, kp, vp, table


def _masked_decode(q, k, v, lens, window):
    """XLA decode over the contiguous cache with a ``[len - window, len)``
    mask: what a window layer's step has to give."""
    b, hq, d = q.shape
    g = hq // k.shape[1]
    s = np.einsum("bhgd,bhsd->bhgs", q.reshape(b, -1, g, d), k) / np.sqrt(d)
    pos = np.arange(S_MAX)[None, :]
    live = (pos < lens[:, None]) & (pos >= lens[:, None] - window)
    s = np.where(live[:, None, None, :], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bhgs,bhsd->bhgd", p, v).reshape(b, hq, d)


@pytest.mark.parametrize("fuse_heads", [True, False])
@pytest.mark.parametrize("pages_per_step", [None, 1])
def test_window_kernel_against_masked_xla_decode(fuse_heads, pages_per_step):
    """The window kernel (interpreted) over a RING against a masked decode
    over the whole cache, lengths below, at and past the window and past
    ring wraps; and its XLA twin (the CPU tests' only) the same."""
    rng = np.random.default_rng(3)
    b, hq, h_kv, d, ring = 6, 4, 2, 128, 3
    lens = np.array([1, 5, 8, 13, 24, 32], np.int32)
    k, v, kp, vp, table = _ring_pools(rng, b, h_kv, d, lens, ring)
    q = rng.standard_normal((b, hq, d)).astype(np.float32)
    want = _masked_decode(q, k, v, lens, WINDOW)
    args = [jnp.asarray(x) for x in (q, kp, vp, lens, table)]
    got, lse = fd.paged_flash_decode(
        *args, window=WINDOW, fuse_heads=fuse_heads,
        pages_per_step=pages_per_step, return_lse=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    twin, lse_twin = fd._xla_paged_window_decode(
        *args, window=WINDOW, return_lse=True)
    np.testing.assert_allclose(np.asarray(twin), want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_twin),
                               rtol=1e-5, atol=1e-5)


def test_window_kernel_reads_a_slot_of_length_zero_as_nothing():
    rng = np.random.default_rng(4)
    k, v, kp, vp, table = _ring_pools(rng, 2, 2, 128, np.array([0, 3]), 3)
    q = rng.standard_normal((2, 4, 128)).astype(np.float32)
    out, lse = fd.paged_flash_decode(
        *(jnp.asarray(x) for x in (q, kp, vp, np.array([0, 3], np.int32), table)),
        window=WINDOW, return_lse=True, interpret=True)
    assert not np.asarray(out[0]).any() and np.isneginf(np.asarray(lse[0])).all()
    assert np.isfinite(np.asarray(lse[1])).all()


def test_a_window_of_s_max_is_the_dense_familys_attention(toy):
    """Decode: the window kernel with ``window = s_max`` over a full-width
    table gives what the dense family's call (``window=None``) gives on
    the same pools. Prefill: the band with ``window >= L`` gives the dense
    family's causal attention on the same q, k, v."""
    cfg, _, _, _ = toy
    rng = np.random.default_rng(5)
    b, hq, h_kv, d, pages = 3, 4, 2, 128, S_MAX // PAGE
    lens = np.array([2, 17, 32], np.int32)
    kp = rng.standard_normal((b * pages, h_kv, PAGE, d)).astype(np.float32)
    vp = rng.standard_normal(kp.shape).astype(np.float32)
    table = rng.permutation(b * pages).reshape(b, pages).astype(np.int32)
    q = rng.standard_normal((b, hq, d)).astype(np.float32)
    args = [jnp.asarray(x) for x in (q, kp, vp, lens, table)]
    dense = fd.paged_flash_decode(*args, interpret=True)
    whole = fd.paged_flash_decode(*args, window=S_MAX, interpret=True)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(dense),
                               rtol=1e-6, atol=1e-6)
    L = 11
    q4 = jnp.asarray(rng.standard_normal((2, L, hq, 8)), jnp.float32)
    k4 = jnp.asarray(rng.standard_normal((2, L, h_kv, 8)), jnp.float32)
    v4 = jnp.asarray(rng.standard_normal((2, L, h_kv, 8)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(window_moe.banded_attention(q4, k4, v4, S_MAX)),
        np.asarray(_causal_gqa_attention(q4, k4, v4, cfg)), **TOL)


def test_the_dense_walk_and_a_window_of_s_max_agree_on_poisoned_tables():
    """Both forms walk a row's live pages and no others: over a pool whose
    dead pages hold a large finite value, with every table column past a
    row's last live page naming such a page, ``window=None`` and ``window
    = s_max`` give the masked decode over the contiguous cache."""
    from tests.test_flash_decode import _poisoned_pools

    rng = np.random.default_rng(6)
    lens = np.array([1, PAGE, 3 * PAGE + 1, S_MAX], np.int32)
    k, v, kp, vp, table = _poisoned_pools(
        rng, lens, 2, 128, None, page=PAGE, pages=S_MAX // PAGE)
    q = rng.standard_normal((len(lens), 4, 128)).astype(np.float32)
    want = _masked_decode(q, k, v, lens, S_MAX)
    args = [jnp.asarray(x) for x in (q, kp, vp, lens, table)]
    for window in (None, S_MAX, 2 * S_MAX):
        got = fd.paged_flash_decode(*args, window=window, interpret=True)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_the_dense_call_lowers_to_the_parents_program():
    """``window=None``: the jaxpr of ``paged_flash_decode`` at one shape is,
    letter for letter, the one kept under tests/golden/ (regenerated by
    PR 38, which moved the page loop into the kernel on purpose: the file
    is the dense cells' kernel as that PR left it), so a later edit that
    means the window form alone cannot move the dense cells' kernel
    unseen; and the kernels keep the names the benchmark's readers find."""
    b, hq, h_kv, d, page, pages = 4, 8, 2, 128, 16, 8
    q = jax.ShapeDtypeStruct((b, hq, d), jnp.bfloat16)
    pool = jax.ShapeDtypeStruct((b * pages, h_kv, page, d), jnp.bfloat16)
    lens = jax.ShapeDtypeStruct((b,), jnp.int32)
    table = jax.ShapeDtypeStruct((b, pages), jnp.int32)

    def call(window):
        return str(jax.make_jaxpr(
            lambda q, k, v, n, t: fd.paged_flash_decode(
                q, k, v, n, t, return_lse=True, interpret=True, window=window)
        )(q, pool, pool, lens, table))

    with open(GOLDEN) as f:
        assert call(None) + "\n" == f.read()
    windowed = call(2 * page)
    assert "name=paged_flash_decode_w32_fh" in windowed
    assert "name=paged_flash_decode_fh" in call(None)


def test_the_eight_shares_of_the_bank_add_up_to_the_uncut_layer(toy, ref):
    """The guide's share test: one expert layer's MLP run once per share
    of the bank (8 shares of 1 expert), what every chip computes alike
    (the shared expert) counted once, equals the uncut reference."""
    cfg, params, plain, _ = toy
    p, w = params["layers"][1], plain[1]
    h = jax.random.normal(jax.random.PRNGKey(4), (24, cfg.hidden), jnp.float32)
    want = ref.moe_part(h, w, False)                      # all 8 experts
    whole, stats = gated_experts.moe_mlp(cfg, h, p, 8)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want), **TOL)
    total, hit = 0.0, 0
    for first in range(8):
        share = dataclasses.replace(cfg, experts_held=(first, 1))
        bank = dict(p, we_gate_up=p["we_gate_up"][first:first + 1],
                    we_down=p["we_down"][first:first + 1])
        y, st = gated_experts.moe_mlp(share, h, bank, 8)
        total, hit = total + y, hit + int(st[1])
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), **TOL)
    assert hit == int(stats[1]) == 24 * 2      # every assignment, once
    # and the reference, given a share, gives that share's part
    ref.configure(dict(TOY, num_experts=2, experts_held=[2, 2],
                       published=dict(num_experts=8)))
    try:
        share = dataclasses.replace(cfg, experts_held=(2, 2))
        bank = dict(p, we_gate_up=p["we_gate_up"][2:4], we_down=p["we_down"][2:4])
        y, _ = gated_experts.moe_mlp(share, h, bank, 8)
        part = ref.moe_part(h, dict(w, **{k: w[k][2:4] for k in (
            "we_gate", "we_up", "we_down")}), False)
        # the reference's share adds the shared expert; this one does not
        # hold expert 0, so the program leaves it to the share that does
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(part - ref.shared_part(h, w, False)),
            **TOL)
    finally:
        ref.configure(TOY)


def test_a_sliced_head_is_the_same_rows_of_the_whole_head(toy):
    """``vocab_held``: a share that holds rows 32..95 of embedding and
    head gives, on ids counted from 32, the whole model's logits at those
    rows."""
    cfg, params, _, _ = toy
    first, count = 32, 64
    part_cfg = dataclasses.replace(cfg, vocab=count, vocab_held=(first, count))
    part = window_moe.slice_vocab(params, first, count)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 10), first,
                                first + count)
    whole = window_moe.forward_logits(cfg, params, tokens)
    got = window_moe.forward_logits(part_cfg, part, tokens - first)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(whole[..., first:first + count]),
        rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError, match="the slice IS the vocabulary"):
        dataclasses.replace(cfg, vocab_held=(0, 64))


def _refusals(cfg, params):
    from triton_dist_tpu.models.prefix_cache import PrefixCacheConfig
    from triton_dist_tpu.serving.disagg import DisaggServingEngine
    from triton_dist_tpu.serving.speculative import (
        SpecDecodeConfig, SpeculativeBatcher,
    )

    one = _mesh(cfg)
    two = Mesh(np.array(jax.devices()[:2]), (cfg.axis,))
    spec = WindowPagedKVCacheSpec(S_MAX, PAGE, static_table=True)
    kw = dict(s_max=S_MAX, page_size=PAGE)
    return {
        "prefix cache": ("prefix_cache", lambda: ContinuousBatcher(
            cfg, params, one, prefill=True,
            prefix_cache=PrefixCacheConfig(), **kw)),
        "ranged prefill": ("ranged prefill", lambda: ContinuousBatcher(
            cfg, params, one, prefill=True, prefill_chunk_tokens=8, **kw)),
        "contiguous cache": ("contiguous cache", lambda: ContinuousBatcher(
            cfg, params, one, s_max=S_MAX)),
        "wider mesh": ("wider than one device", lambda: ContinuousBatcher(
            cfg, params, two, **kw)),
        "wider mesh, the spec": ("one-device shard", lambda: spec.init(cfg, 2)),
        "verify": ("speculative verify", lambda: spec.update_multi_and_attend()),
        "speculative decoding": (
            "speculative decoding", lambda: SpeculativeBatcher(
                cfg, params, one, spec_decode=SpecDecodeConfig(), **kw)),
        "handoff": ("disaggregated handoff", lambda: DisaggServingEngine(
            cfg, params, two, **kw)),
        "scratch page": ("prefix cache", lambda: WindowPagedKVCacheSpec(
            S_MAX, PAGE, static_table=True, extra_pages=1).init(cfg, 1)),
    }


@pytest.mark.parametrize("what", [
    "prefix cache", "ranged prefill", "contiguous cache", "wider mesh",
    "wider mesh, the spec", "verify", "speculative decoding", "handoff",
    "scratch page"])
def test_what_a_ring_cannot_serve_is_refused_by_name(toy, what):
    cfg, params, _, _ = toy
    match, build = _refusals(cfg, params)[what]
    with pytest.raises(NotImplementedError, match=match) as err:
        build()
    assert "kv_window" in str(err.value) or "one-device" in str(err.value)


# the limits a toy float32 run passes with room (its gaps are rounding:
# every served token is the reference's best or ties it) and anything
# wrong breaks
LIMITS = dict(max_gap=1e-3, mean_gap=1e-4)


def _verdict(ref, plain, outer, prompt, out):
    seq = np.array([list(prompt) + list(out)])
    want = _ref_logits(ref, plain, outer, seq)
    first = len(prompt) - 1
    gap, exact = ref.gaps(jnp.asarray(want[:, first:first + len(out)]),
                          np.array([out]))
    return correct.verdict(dict(
        max_gap=float(gap.max()), mean_gap=float(gap.mean()), failed=0,
        health_flips=0, tokens_compared=len(out)), LIMITS)


def _serve_one(cfg, params, prompt, n_new, tamper=None):
    batcher = ContinuousBatcher(
        cfg, params, _mesh(cfg), s_max=S_MAX, page_size=PAGE, prefill=True)
    if tamper is not None:
        tamper(batcher)
    batcher.submit(Request(list(prompt), n_new, uid="a"))
    batcher.submit(Request(list(prompt[::-1]), n_new, uid="b"))
    return dict(batcher.run())["a"]


def _mispoint_a_ring_page(batcher):
    """Slot 0's second ring page is slot 1's: two requests write and read
    the same window rows."""
    table = np.array(batcher.cache["block_table_win"])
    table[:, 0, 1] = table[:, 1, 1]
    batcher.cache = dict(batcher.cache, block_table_win=jax.device_put(
        table, batcher.cache["block_table_win"].sharding))


@pytest.mark.parametrize("fault", ["none", "ring page mispointed",
                                   "window off by one"])
def test_a_planted_fault_is_not_correct(toy, ref, served, fault):
    """Through ``correct.verdict``, the comparison that decides a cell's
    ``correct``: the sound run passes the toy limits; a ring page pointed
    at a neighbour's, and a window of 7 for 8, do not."""
    cfg, params, plain, outer = toy
    prompt = served[0]["wrapped"].prompt
    if fault == "none":
        ok, _ = _verdict(ref, plain, outer, prompt, served[1]["wrapped"])
        assert ok
        return
    if fault == "window off by one":
        out = _serve_one(dataclasses.replace(cfg, window=WINDOW - 1), params,
                         prompt, 10)
    else:
        out = _serve_one(cfg, params, prompt, 10, _mispoint_a_ring_page)
    ok, numbers = _verdict(ref, plain, outer, prompt, out)
    assert not ok
    assert {k for k, (v, lim) in numbers.items() if v > lim} & {
        "max_gap", "mean_gap"}


def test_the_lower_precision_control_is_far_outside_the_tolerances(toy, ref):
    """The reference as W8A8 int8: its logits differ from the reference's
    by far more than ``TOL``, and the token it puts first breaks the toy
    limits, so the comparisons above would catch a lower precision."""
    cfg, _, plain, outer = toy
    tokens = jax.random.randint(jax.random.PRNGKey(9), (2, 24), 0, cfg.vocab)
    want = _ref_logits(ref, plain, outer, tokens)
    low = _ref_logits(ref, plain, outer, tokens, control=True)
    assert np.abs(low - want).max() > 50 * TOL["atol"]
    gap, _ = ref.gaps(jnp.asarray(want), low.argmax(-1))
    ok, _ = correct.verdict(dict(
        max_gap=float(gap.max()), mean_gap=float(gap.mean()), failed=0,
        health_flips=0, tokens_compared=gap.size), LIMITS)
    assert not ok


def test_engine_serves_it_with_lookahead_and_the_spans_carry_the_counters(
        toy, ref, adapter):
    """A SHARE of the toy configuration (4 of 8 experts held) through
    ``ServingEngine`` with lookahead: the same entry, scheduler and spans
    as the other families, the family's counters on the round's and the
    admission's spans and the banks' bytes on the intake's."""
    from triton_dist_tpu import config as tdt_config, obs
    from triton_dist_tpu.obs import ObsConfig
    from triton_dist_tpu.resilience import retry
    from triton_dist_tpu.serving import Arrival, ServingConfig, ServingEngine
    from triton_dist_tpu.serving.engine import Finished

    cfg, params, _, _ = toy
    cfg = dataclasses.replace(cfg, experts_held=(0, 4))
    params = dict(params, layers=[
        dict(p, **{k: p[k][:4] for k in ("we_gate_up", "we_down") if k in p})
        for p in params["layers"]])
    before = tdt_config.get_config().obs
    tdt_config.update(obs=ObsConfig(spans=True))
    obs.reset()
    try:
        clock = retry.FakeClock()
        with retry.clock_scope(clock):
            eng = ServingEngine(
                cfg, params, _mesh(cfg), s_max=S_MAX, page_size=PAGE,
                prefill=True, lookahead=True, clock=clock,
                serving=ServingConfig(virtual_step_s=0.01))
            rng = np.random.default_rng(1)
            done = eng.serve([
                Arrival(0.0, Request(list(rng.integers(0, cfg.vocab, n)), 6,
                                     uid=f"u{n}"))
                for n in (6, 9)])
            assert eng._batcher.rounds_ahead > 0
        assert all(isinstance(done[f"u{n}"], Finished) for n in (6, 9))
        spans = obs.spans()
    finally:
        tdt_config.update(obs=before)
        obs.reset()
    by_name = {}
    for sp in spans:
        by_name.setdefault(sp.name, []).append(sp.attrs)
    assert by_name["tdt.batcher.take_params"][0]["expert_bytes"] == \
        gated_experts.expert_bytes(params)
    rounds = by_name["tdt.batcher.decode_round"]
    admits = by_name["tdt.batcher.admit_prefill"]
    assert len(admits) == 2 and rounds
    for attrs in rounds:
        # 2 slots x top-2 x 3 expert layers, half the bank held here
        assert attrs["assignments"] + attrs["assignments_elsewhere"] == 2 * 2 * 3
        assert attrs["experts_hit"] <= min(attrs["assignments"], 3 * 4)
        assert attrs["expert_load_max"] <= 2
        # 3 window layers read at most the window, the full layer all
        assert 0 < attrs["window_rows"] <= 3 * 2 * WINDOW
        assert attrs["full_rows"] >= attrs["window_rows"] // 3
        # 2 slots x (the full layer's 8 pages + 3 window layers' rings of
        # 3): the tables' side is capacity; the walk's follows the lengths
        assert attrs["kv_pages_table"] == 2 * (S_MAX // PAGE + 3 * 3)
        assert 0 < attrs["kv_pages_live"] < attrs["kv_pages_table"]
    assert any(a["assignments_elsewhere"] > 0 for a in rounds)
    # both slots live and past the window: 3 layers x 2 slots x 8 rows,
    # while the full layer's rows go on growing
    late = [a for a in rounds if a["window_rows"] == 3 * 2 * WINDOW]
    assert late and max(a["full_rows"] for a in late) > 2 * WINDOW
    for attrs in admits:
        # the admitted slot's rows: bucket x top-2 x 3 expert layers
        assert (attrs["assignments"] + attrs["assignments_elsewhere"]
                == attrs["bucket"] * 2 * 3)
        assert attrs["window_rows"] == attrs["full_rows"] == 0
