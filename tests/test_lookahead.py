"""Lookahead, what ``ContinuousBatcher`` does unless told not to: a round
that cannot change the schedule sends the next step, fed on the device,
before it pulls its own tokens. What must hold: the same tokens in the same
rounds as the plain batcher (``lookahead=False``, the reference here), a
step sent in vain thrown away, and no step sent where the round's tokens
decide what runs next."""
from __future__ import annotations

import dataclasses
import functools

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from triton_dist_tpu.models import init_params
from triton_dist_tpu.models.decode import ContinuousBatcher, Request
from triton_dist_tpu.models.tp_transformer import TransformerConfig
from triton_dist_tpu.ops.allgather_gemm import AGGemmConfig
from triton_dist_tpu.ops.gemm_reduce_scatter import GemmRSConfig
from triton_dist_tpu.resilience import retry
from triton_dist_tpu.serving import ServingConfig, ServingEngine
from triton_dist_tpu.serving.speculative import (
    SpecDecodeConfig, SpeculativeBatcher,
)
from triton_dist_tpu.serving.traffic import Arrival


@pytest.fixture(scope="module")
def mesh1() -> Mesh:
    return Mesh(np.array(jax.devices()[:1]), ("tp",))


@pytest.fixture(scope="module")
def mesh4() -> Mesh:
    return Mesh(np.array(jax.devices()[:4]), ("tp",))


def _tiny(n_kv_heads):
    cfg = TransformerConfig(
        vocab=32, hidden=32, ffn=64, n_layers=1, n_q_heads=4,
        n_kv_heads=n_kv_heads, head_dim=8, batch=2, seq=8,
        ag_config=AGGemmConfig(8, 16, 16), rs_config=GemmRSConfig(8, 16, 16))
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def tiny():
    return _tiny(2)


def _reqs(cfg, shapes, prompts=5, **kw):
    rng = np.random.default_rng(prompts)
    return [Request([int(t) for t in rng.integers(0, cfg.vocab, n)], mx,
                    uid=i, **kw) for i, (n, mx) in enumerate(shapes)]


def _run(cfg, params, mesh, reqs, late=(), **kw):
    """Tokens by uid, rounds run, and the batcher; ``late`` requests are
    submitted after the third step, into a slot left idle until then."""
    b = ContinuousBatcher(cfg, params, mesh, s_max=32, **kw)
    for r in reqs:
        b.submit(r)
    for _ in range(3):
        b.step()
    for r in late:
        b.submit(r)
    return dict(b.run(max_steps=400)), b.rounds, b


def _same_tokens_in_the_same_rounds(cfg, params, mesh, shapes, **kw):
    want, rounds, plain = _run(cfg, params, mesh, _reqs(cfg, shapes),
                               lookahead=False, **kw)
    got, rounds2, b = _run(cfg, params, mesh, _reqs(cfg, shapes), **kw)
    assert got == want and rounds2 == rounds
    assert plain.rounds_ahead == 0 and plain._ahead is None
    # most rounds had their step sent by the round before; none in vain (a
    # step goes ahead only where no slot can free), none left in flight
    assert b.rounds_ahead > rounds // 3
    assert b.ahead_discarded == 0 and b._ahead is None


@pytest.mark.parametrize("kw", [
    dict(),                                     # token-fed, contiguous cache
    dict(prefill=True),
    dict(prefill=True, page_size=8),
], ids=["token_fed", "prefill", "prefill_paged"])
def test_lookahead_serves_the_same_tokens_in_the_same_rounds(tiny, mesh1, kw):
    cfg, params = tiny
    _same_tokens_in_the_same_rounds(
        cfg, params, mesh1,
        [(3, 9), (5, 4), (2, 7), (6, 1), (4, 2), (3, 12)], **kw)


@pytest.mark.parametrize("kw", [
    dict(),
    dict(prefill=True, page_size=8),
], ids=["contiguous", "prefill_paged"])
def test_a_slot_whose_request_left_stands_where_an_unused_one_does(
        tiny, mesh1, kw):
    """The step advances every slot and is not told which are live: a
    slot that kept its request's last token and position re-read that
    whole context every round, and an expert layer routed its row to the
    same experts every round, which a share of a bank then fetched for
    nothing. A request that leaves hands its slot back at token 0,
    position 0, and the request beside it is served what it gets alone."""
    cfg, params = tiny
    shapes = [(5, 12), (6, 2)]
    alone, _, _ = _run(cfg, params, mesh1, _reqs(cfg, shapes)[:1], **kw)
    b = ContinuousBatcher(cfg, params, mesh1, s_max=32, **kw)
    for r in _reqs(cfg, shapes):
        b.submit(r)
    while not any(uid == 1 for uid, _ in b.finished):
        b.step()
    i = b.slot_req.index(None)
    assert b.slot_req[1 - i].uid == 0
    assert (b.tok[i], b.pos[i]) == (0, 0) and b.pos[1 - i] > 0
    got = dict(b.run(max_steps=100))
    assert got[0] == alone[0] and len(got[0]) == 12
    assert not b.pos.any() and not b.tok.any()


def test_lookahead_holds_on_a_mesh_of_four(mesh4):
    """The step sent ahead under ``jit_shard_map`` over four devices: its
    inputs advanced and placed replicated, the cache donated to a step
    sent while the argmax before it is still queued. A page a device, so
    the answers cross onto the second device's rows; one request (an
    interpreted admission on four devices is ~14 s), the other slot idle."""
    cfg, params = _tiny(4)
    _same_tokens_in_the_same_rounds(
        cfg, params, mesh4, [(6, 5)],
        prefill=True, page_size=8)


def _on_a_64_byte_boundary(like):
    raw = np.zeros(like.nbytes + 64, np.uint8)
    start = -raw.ctypes.data % 64
    out = raw[start:start + like.nbytes].view(like.dtype)
    out[:] = like
    return out


@pytest.mark.parametrize("n_dev", [1, 4])
def test_a_rounds_inputs_are_the_devices_own(n_dev):
    """The advance program may still be queued when a round's pull returns
    and the host writes ``tok`` / ``pos`` in place, and the CPU backend
    aliases a host array that lies on a 64-byte boundary (where a small
    numpy array lands depends on what the process allocated before): on a
    mesh of two, one run in eight served a position twice. What a round
    hands the device is a copy."""
    cfg, params = _tiny(4)
    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("tp",))
    b = ContinuousBatcher(cfg, params, mesh, s_max=32)
    b.tok, b.pos = _on_a_64_byte_boundary(b.tok), _on_a_64_byte_boundary(b.pos)
    b.tok[:], b.pos[:] = 7, 3
    tok_d, pos_d, logits = b._round_inputs()
    b.tok[:], b.pos[:] = 9, 4
    assert logits is None
    assert np.asarray(tok_d).tolist() == [7, 7]
    assert np.asarray(pos_d).tolist() == [3, 3]


def _compilations() -> list:
    """A list that grows by one entry a backend compilation from now on."""
    from jax import monitoring

    compiled = []
    monitoring.register_event_duration_secs_listener(
        lambda event, *a, **kw: compiled.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    return compiled


def test_nothing_compiles_after_the_first_step_sent_ahead(tiny, mesh1):
    """A warm-up of three tokens a slot sends one step ahead; a longer run
    after it (steps sent ahead of steps sent ahead) compiles nothing."""
    cfg, params = tiny
    b = ContinuousBatcher(cfg, params, mesh1, s_max=32, prefill=True,
                          page_size=8)
    for r in _reqs(cfg, [(3, 3), (5, 3)]):
        b.submit(r)
    b.run(max_steps=50)
    assert b.rounds_ahead == 1
    compiled = _compilations()
    for r in _reqs(cfg, [(3, 9), (5, 7)], prompts=8):
        b.submit(r)
    b.run(max_steps=50)
    assert b.rounds_ahead > 6 and not compiled


@pytest.mark.parametrize("kw", [
    dict(), dict(prefill=True, page_size=8),
], ids=["token_fed", "prefill_paged"])
def test_a_step_sent_in_vain_is_thrown_away(tiny, mesh1, kw):
    """One request on two slots, a second submitted while a step is out
    ahead: its admission moves the cache (and, token-fed, the slot), so the
    step already sent is not the next round's; the tokens stay the plain
    batcher's."""
    cfg, params = tiny
    first, late = _reqs(cfg, [(3, 12)]), _reqs(cfg, [(4, 6)], prompts=6)
    late[0].uid = "late"
    want, rounds, _ = _run(cfg, params, mesh1, first, late, lookahead=False,
                           **kw)
    got, rounds2, b = _run(cfg, params, mesh1, first, late, **kw)
    assert got == want and rounds2 == rounds
    assert b.ahead_discarded == 1 and b.rounds_ahead > 0


def _serve(cfg, params, mesh, trace, **kw):
    eng = ServingEngine(cfg, params, mesh, s_max=32, prefill=True,
                        page_size=8, clock=retry.FakeClock(),
                        serving=ServingConfig(virtual_step_s=0.05), **kw)
    done = eng.serve(trace)
    return {u: r.tokens for u, r in done.items()}, eng


def test_an_arrival_while_a_slot_decodes_pays_one_step(tiny, mesh1):
    """Through ``ServingEngine`` built with no keyword: the arrival finds
    one step already queued, which is thrown away and run again after its
    admission; no token changes, and the engine's snapshot says so."""
    cfg, params = tiny
    a, b = _reqs(cfg, [(3, 12), (4, 6)])
    trace = [Arrival(0.0, a), Arrival(0.22, b)]
    want, plain = _serve(cfg, params, mesh1, trace, lookahead=False)
    got, eng = _serve(cfg, params, mesh1, trace)
    assert got == want and len(got[1]) == 6
    rounds = eng.snapshot()["batcher"]
    assert rounds["ahead_discarded"] == 1
    assert rounds["rounds"] == plain.snapshot()["batcher"]["rounds"]
    assert plain.snapshot()["batcher"]["rounds_ahead"] == 0


def test_the_engine_snapshot_counts_rounds_ahead_across_a_rebuild(
        tiny, mesh1):
    cfg, params = tiny
    reqs = _reqs(cfg, [(3, 9), (5, 7)])
    got, eng = _serve(cfg, params, mesh1, [Arrival(0.0, r) for r in reqs])
    before = eng.snapshot()["batcher"]
    assert set(before) == {"rounds", "rounds_ahead", "ahead_discarded"}
    assert before["rounds"] > before["rounds_ahead"] > 0
    assert before["ahead_discarded"] == 0
    # a rebuild's batcher counts from 0: the retired one's rounds are kept
    eng._rebuild("test")
    assert eng._batcher.rounds == 0 and eng.snapshot()["batcher"] == before


@pytest.mark.parametrize("kw", [
    dict(eos_id=31), dict(temperature=0.7, seed=3),
], ids=["stop_token", "sampled"])
def test_no_step_goes_ahead_where_the_tokens_decide(tiny, mesh1, kw):
    cfg, params = tiny
    shapes = [(3, 6), (5, 4)]
    want, _, _ = _run(cfg, params, mesh1, _reqs(cfg, shapes, **kw),
                      lookahead=False)
    got, _, b = _run(cfg, params, mesh1, _reqs(cfg, shapes, **kw))
    assert got == want and b.rounds_ahead == 0 and b.ahead_discarded == 0


def test_weights_swapped_under_a_step_sent_ahead(tiny, mesh1):
    cfg, params = tiny
    other = init_params(jax.random.PRNGKey(1), cfg)
    outs = []
    for look in (False, True):
        b = ContinuousBatcher(cfg, params, mesh1, s_max=32, lookahead=look)
        b.submit(_reqs(cfg, [(3, 10)])[0])
        for _ in range(5):
            b.step()
        b.params = other
        outs.append(dict(b.run(max_steps=100)))
        assert b.ahead_discarded == int(look)
    assert outs[0] == outs[1]


@pytest.mark.parametrize("kw", [dict(), dict(lookahead=True)],
                         ids=["no_keyword", "explicit"])
def test_speculative_batcher_runs_plain_rounds_and_refuses_lookahead(
        tiny, mesh1, kw):
    """Its rounds decide their inputs from pulled tokens: built with no
    keyword it hands ``lookahead=False`` on by itself; asked for it, it
    refuses."""
    cfg, params = tiny
    build = functools.partial(
        SpeculativeBatcher, cfg, params, mesh1, s_max=32,
        spec_decode=SpecDecodeConfig(k=0), **kw)
    if kw:
        with pytest.raises(NotImplementedError, match="lookahead"):
            build()
        return
    b = build()
    assert b.lookahead is False
    b.submit(_reqs(cfg, [(3, 5)])[0])
    assert len(dict(b.run(max_steps=50))[0]) == 5 and b.rounds_ahead == 0


# -- the admission's discipline: a whole-batch pass fills its rows ---------------
# (docs/serving.md "The admission's discipline"). The dense family's bucket
# prefill runs every slot's rows, so ``_admit`` hands the queued requests of
# one bucket to ONE pass; what a request is served must not depend on who
# shares its pass.

def _mixed(cfg, shapes, prompts=11):
    """Requests of ``shapes``; every odd one samples on its own seed."""
    reqs = _reqs(cfg, shapes, prompts)
    for r in reqs[1::2]:
        r.temperature, r.seed = 0.8, 3 + r.uid
    return reqs


def _admitted(cfg, params, mesh, reqs, a_pass_each=False, before=None, **kw):
    """A batcher that has admitted ``reqs`` and run no round: as a backlog
    (the sweep's requests of a bucket share a pass) or, the old order, an
    ``_admit`` a request. ``before(batcher)`` runs on the new batcher."""
    b = ContinuousBatcher(cfg, params, mesh, s_max=32, prefill=True, **kw)
    if before is not None:
        before(b)
    for r in reqs:
        b.submit(r)
        if a_pass_each:
            b._admit()
    b._admit()
    return b


def _served(cfg, params, mesh, reqs, a_pass_each=False, **kw):
    """Tokens by uid and the batcher: ``reqs`` queued at once or, the old
    order, one submitted a step (each then finds its slot alone)."""
    b = ContinuousBatcher(cfg, params, mesh, s_max=32, prefill=True, **kw)
    for r in reqs:
        b.submit(r)
        if a_pass_each:
            b.step()
    return dict(b.run(max_steps=400)), b


@pytest.mark.parametrize("kw", [dict(), dict(page_size=8)],
                         ids=["contiguous", "paged"])
def test_a_backlog_of_mixed_buckets_is_served_a_request_a_passes_tokens(
        tiny, mesh1, kw):
    """Buckets 4 and 8 queued at once on two slots: the first sweep's two
    share a pass, and so do the two of bucket 8 whose slots free in one
    round; the last two differ in bucket. Request by request the tokens
    are those of a pass each, greedy and sampled, on a fresh slot and on
    one that served before."""
    cfg, params = tiny
    shapes = [(3, 3), (4, 3), (5, 3), (6, 3), (3, 2), (7, 3)]
    want, alone = _served(cfg, params, mesh1, _mixed(cfg, shapes),
                          a_pass_each=True, **kw)
    got, b = _served(cfg, params, mesh1, _mixed(cfg, shapes), **kw)
    assert got == want and set(got) == set(range(len(shapes)))
    assert (alone.prefill_passes_total, b.prefill_passes_total) == (6, 4)
    assert b.prefill_tokens_total == alone.prefill_tokens_total == sum(
        n for n, _ in shapes)


def test_a_shared_pass_on_a_mesh_of_four_leaves_what_a_pass_each_leaves(
        mesh4):
    """Under ``jit_shard_map`` over four devices the pass's rows are dealt
    to the devices by position and the mask gates each device's page
    writes: two requests in one pass leave, bit for bit, the cache, the
    first tokens and the positions that a pass each leaves. No round: an
    interpreted admission on four devices is ~14 s, and a round after it
    reads nothing else."""
    cfg, params = _tiny(4)
    want, got = (_admitted(cfg, params, mesh4, _mixed(cfg, [(6, 3), (5, 3)]),
                           a_pass_each=each, page_size=8)
                 for each in (True, False))
    assert (want.prefill_passes_total, got.prefill_passes_total) == (2, 1)
    assert got.slot_out == want.slot_out and len(got.slot_out[1]) == 1
    assert got.tok.tolist() == want.tok.tolist()
    assert got.pos.tolist() == want.pos.tolist() == [6, 5]
    for pool in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(got.cache[pool]),
                                      np.asarray(want.cache[pool]))


def test_a_member_that_finishes_in_its_pass_frees_its_slot_for_the_queue(
        tiny, mesh1):
    """``max_new_tokens=1`` ends a member inside its group: its slot goes
    to the next queued request in the same ``_admit``, in a second pass."""
    cfg, params = tiny
    shapes = [(3, 1), (4, 3), (3, 4)]
    want = {r.uid: _served(cfg, params, mesh1, [r])[0][r.uid]
            for r in _reqs(cfg, shapes)}
    b = ContinuousBatcher(cfg, params, mesh1, s_max=32, prefill=True)
    for r in _reqs(cfg, shapes):
        b.submit(r)
    b._admit()
    assert b.finished == [(0, want[0])] and len(want[0]) == 1
    assert [r.uid for r in b.slot_req] == [2, 1] and not b.queue
    assert b.prefill_passes_total == 2
    assert dict(b.run(max_steps=50)) == want


@pytest.mark.parametrize("model, kw, shares", [
    ("MoETransformerConfig", dict(), True),
    ("EPMoETransformerConfig", dict(), True),
    ("EPMoETransformerConfig", dict(ep_max_m=10), False),
], ids=["tp_moe", "ep_moe", "ep_moe_capacity"])
def test_a_routed_stand_in_shares_a_pass_unless_a_capacity_is_set(
        mesh1, model, kw, shares):
    """The routed stand-ins' forward runs every row too. Their grouped
    GEMM and their exchange at its worst-case slab keep a row's sums its
    own, so they share a pass bit for bit; an exchange with a set
    capacity drops the assignments past it in row order, so what a
    request keeps depends on who lies before it in the pass (two layers
    on two devices, capacity 10: another first token and cache), and it
    admits a request a pass."""
    from triton_dist_tpu import models
    from triton_dist_tpu.ops.group_gemm import GroupGemmConfig

    cfg = getattr(models, model)(
        vocab=32, hidden=32, ffn=64, n_layers=1, n_q_heads=4, n_kv_heads=2,
        head_dim=8, batch=2, seq=8, n_experts=4, topk=2,
        ag_config=AGGemmConfig(8, 16, 16), rs_config=GemmRSConfig(8, 16, 16),
        gg_config=GroupGemmConfig(4, 32, 32), **kw)
    params = models.init_moe_params(jax.random.PRNGKey(0), cfg)

    reqs = _reqs(cfg, [(3, 3), (4, 3)])
    got = _admitted(cfg, params, mesh1, reqs)
    assert got._fills_rows is shares
    assert got.prefill_passes_total == (1 if shares else 2)
    if shares:
        want = _admitted(cfg, params, mesh1, reqs, a_pass_each=True)
        assert got.slot_out == want.slot_out
        for pool in ("k", "v"):
            np.testing.assert_array_equal(np.asarray(got.cache[pool]),
                                          np.asarray(want.cache[pool]))


# -- on one device the pass walks its members (PR 47) -------------------------
# One prefill program a bucket: a ``fori_loop`` over the pass's members, a trip
# count that is data, each trip one member's ``[1, bucket]`` rows into its own
# slot. A mesh of several devices keeps the masked whole-batch pass.

def _four_slots(n_kv_heads=2):
    cfg, params = _tiny(n_kv_heads)
    return dataclasses.replace(cfg, batch=4), params


def _slot_kv(b, i: int) -> list:
    """Slot ``i``'s k and v as the cache holds them: its own pages of the
    pool, or its rows of the contiguous layers."""
    at = (np.asarray(b.cache["block_table"])[0, i]
          if "block_table" in b.cache else i)
    return [np.asarray(b.cache[pool])[:, at] for pool in ("k", "v")]


def _admitted_over_noise(cfg, params, mesh, reqs, **kw):
    """``_admitted`` on a cache that held noise in every slot: the batcher,
    the logit row each request's first token was sampled from, and what
    each slot held before."""
    rows, held = {}, []

    def dirty(b):
        rng = np.random.default_rng(7)
        b.cache = dict(b.cache, **{
            pool: jax.device_put(
                rng.standard_normal(b.cache[pool].shape).astype(
                    b.cache[pool].dtype), b.cache[pool].sharding)
            for pool in ("k", "v")})
        held.extend(_slot_kv(b, i) for i in range(cfg.batch))
        first = b._first_token

        def keep(i, req, last_i):
            rows[req.uid] = np.array(last_i)
            first(i, req, last_i)

        b._first_token = keep

    return _admitted(cfg, params, mesh, reqs, before=dirty, **kw), rows, held


@pytest.mark.parametrize("kw", [dict(), dict(page_size=8)],
                         ids=["contiguous", "paged"])
@pytest.mark.parametrize("m", [1, 2, 4])
def test_a_walked_pass_gives_each_member_what_a_pass_alone_gives(
        mesh1, m, kw):
    """A group of ``m`` of the four slots in ONE pass: each member's first
    token, logit row and k/v are, bit for bit, those of a pass of its own,
    and a slot the pass does not admit keeps what it held."""
    cfg, params = _four_slots()
    shapes = [(6, 3), (5, 3), (7, 3), (8, 3)][:m]
    (want, want_rows, _), (got, rows, held) = (
        _admitted_over_noise(cfg, params, mesh1, _mixed(cfg, shapes),
                             a_pass_each=each, **kw)
        for each in (True, False))
    assert (want.prefill_passes_total, got.prefill_passes_total) == (m, 1)
    assert got.slot_out == want.slot_out
    assert all(len(o) == 1 for o in got.slot_out[:m])
    assert got.pos.tolist() == want.pos.tolist()
    assert set(rows) == set(range(m))
    for i in range(cfg.batch):
        if i < m:
            np.testing.assert_array_equal(rows[i], want_rows[i])
        for ours, theirs, before in zip(
                _slot_kv(got, i), _slot_kv(want, i), held[i]):
            np.testing.assert_array_equal(ours, theirs)
            assert (i >= m) == np.array_equal(ours, before)


@pytest.mark.parametrize("groups", [(1, 3, 4), (4, 1), (3, 2)],
                         ids=["1_3_4", "4_1", "3_2"])
def test_one_prefill_program_a_bucket_whatever_the_group(mesh1, groups):
    """Groups of every size of one bucket run the ONE program the first
    group compiled: the members are data, the trip count too."""
    cfg, params = _four_slots()
    b = ContinuousBatcher(cfg, params, mesh1, s_max=32, prefill=True,
                          page_size=8)
    compiled = _compilations()
    uid = 0
    for n, m in enumerate(groups):
        for r in _reqs(cfg, [(5 + (uid + j) % 4, 1) for j in range(m)],
                       prompts=uid):
            r.uid, uid = uid, uid + 1
            b.submit(r)
        if n == 1:
            compiled.clear()      # the first group's pass compiled it
        b._admit()
        assert b.prefill_passes_total == n + 1 and not b.queue
    assert b.prefill_bucket_count == 1 and len(b.finished) == sum(groups)
    assert b.prefill_rows_total == sum(groups) * 8 and not compiled


@pytest.mark.parametrize("mesh, whole", [("mesh1", False), ("mesh4", True)])
def test_a_pass_counts_the_rows_it_ran(request, mesh, whole):
    """``rows`` on the pass's span and ``prefill_rows_total``: the
    members' rows where the pass walks them (one device), every slot's
    where it runs masked (four); the pass of four is PR 46's, to the tokens
    a pass each serves there."""
    from triton_dist_tpu import config as tdt_config, obs

    mesh = request.getfixturevalue(mesh)
    cfg, params = _four_slots(4)
    shapes = [(6, 3), (5, 3)]
    before = tdt_config.get_config().obs
    obs.reset()
    tdt_config.update(obs=obs.ObsConfig(spans=True))
    try:
        got = _admitted(cfg, params, mesh, _mixed(cfg, shapes), page_size=8)
        (span,) = [s.attrs for s in obs.spans()
                   if s.name == "tdt.batcher.admit_prefill"]
    finally:
        tdt_config.update(obs=before)
        obs.reset()
    assert got._walks_members is not whole
    rows = (cfg.batch if whole else len(shapes)) * 8
    assert (span["admitted"], span["bucket"], span["rows"]) == (2, 8, rows)
    assert (got.prefill_passes_total, got.prefill_rows_total) == (1, rows)
    want = _admitted(cfg, params, mesh, _mixed(cfg, shapes),
                     a_pass_each=True, page_size=8)
    assert want.prefill_rows_total == 2 * (rows if whole else 8)
    assert got.slot_out == want.slot_out and got.tok.tolist() == want.tok.tolist()
    assert got.pos.tolist() == want.pos.tolist() == [6, 5, 0, 0]
