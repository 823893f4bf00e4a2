"""The one-token convolution over its ring (ops/conv_ring.py) on the CPU,
interpreted: the kernel against its XLA twin and against a plain causal
convolution of the whole sequence. The ring of each case is BUILT from the
sequence (row ``r`` of a slot holds its last input at a position ``== r
(mod K)`` before ``pos``), and every row no position has reached yet holds
NaN: a tap of a position before 0 must read zero by a select."""

import functools
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# (the package exports functions under its modules' names)
cr = importlib.import_module("triton_dist_tpu.ops.conv_ring")

K, LAYERS, LAYER, T = 4, 3, 1, 12
# each slot's position: the sequence's first steps, the last before the
# ring is full, the first with every row live, past one turn and past two,
# and slots everywhere at once
POSITIONS = {
    "first": [0] * 6, "second": [1] * 6, "ring_not_full": [K - 2] * 6,
    "ring_just_full": [K - 1] * 6, "past_one_turn": [K + 1] * 6,
    "past_two_turns": [2 * K + 3] * 6, "mixed": [0, 3, 9, 1, 6, 4],
}


def _case(pos, d=64, stored="float32", seed=0):
    """``(pool, u, pos, w, bias)`` of a step at ``pos [slots]``, with the
    whole sequence ``u_seq [T, slots, d]`` it is a step of."""
    rng = np.random.default_rng(seed)
    slots = len(pos)
    f = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    u_seq, pool = f(T, slots, d), f(LAYERS, K, slots, d)
    pool[LAYER] = np.nan
    for i, p in enumerate(pos):
        for q in range(max(0, p - K + 1), p):
            pool[LAYER, q % K, i] = u_seq[q, i]
    pos = np.asarray(pos)
    u = u_seq[pos, np.arange(slots)]
    w, bias = jnp.asarray(f(K, d), stored), jnp.asarray(f(d), stored)
    return (jnp.asarray(pool), jnp.asarray(u), jnp.asarray(pos, jnp.int32),
            w, bias), u_seq


def _whole_sequence(u_seq, w, bias):
    """``y [T, slots, d]``: the causal depthwise convolution of the whole
    sequence, zeros before its start."""
    w, bias = (np.asarray(x.astype(jnp.float32)) for x in (w, bias))
    padded = np.concatenate([np.zeros_like(u_seq[:K - 1]), u_seq])
    return bias + sum(w[j] * padded[j:j + len(u_seq)] for j in range(K))


def _step(args):
    pool, *rest = args
    return cr.conv_ring_step(pool, LAYER, *rest, interpret=True)


def _check(args, u_seq):
    pool, u, pos, w, bias = args
    y, got = _step(args)
    y_x, want = cr._xla_conv_ring_step(pool, LAYER, u, pos, w, bias)
    slots = np.arange(len(pos))
    y_seq = _whole_sequence(u_seq, w, bias)[np.asarray(pos), slots]
    assert np.isfinite(np.asarray(y)).all()
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_x), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(y), y_seq, rtol=1e-5, atol=1e-5)
    # the ring: the twin's, which is the old one with row pos % K of each
    # slot holding u and nothing else touched (NaN rows compare equal)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    plain = np.array(pool)
    plain[LAYER, np.asarray(pos) % K, slots] = np.asarray(u)
    np.testing.assert_array_equal(np.asarray(got), plain)


@pytest.mark.parametrize("stored", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(POSITIONS))
def test_conv_ring_step_against_its_twin_and_the_whole_sequence(case, stored):
    """Taps and bias in the dtype they are stored in, widened inside."""
    _check(*_case(POSITIONS[case], stored=stored))


def test_a_stale_ring_that_is_not_finite_reads_as_zero_at_position_0():
    """What a finished request left in EVERY row of the slot, the one
    this step writes too: the output is ``bias + w[K - 1] * u``."""
    (pool, u, pos, w, bias), _ = _case([0, 0, 5])
    pool = pool.at[LAYER, :, :2].set(jnp.nan)
    y, got = _step((pool, u, pos, w, bias))
    np.testing.assert_allclose(np.asarray(y[:2]),
                               np.asarray(bias + w[K - 1] * u[:2]),
                               rtol=1e-6, atol=1e-6)
    assert np.isfinite(np.asarray(y)).all()
    np.testing.assert_array_equal(np.asarray(got[LAYER, 0, :2]),
                                  np.asarray(u[:2]))


def test_the_step_run_twice_leaves_the_ring_of_the_step_run_once():
    """A step never reads the row it writes: from the ring the first run
    left, the second gives the same output and the same ring, bit for
    bit (``PAGED_CACHE_KINDS``: a step is repeatable)."""
    (pool, *rest), _ = _case(POSITIONS["mixed"])
    y1, once = _step((pool, *rest))
    y2, twice = _step((once, *rest))
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    np.testing.assert_array_equal(np.asarray(once), np.asarray(twice))


@pytest.mark.parametrize("slots,d", [(3, 128), (5, 64), (8, 1024)])
def test_slots_off_the_sublane_tile_and_a_width_of_one_block(slots, d):
    """Slots that are no multiple of 8 and channels that are one block
    (below the unit of 1024 channels, and the unit itself)."""
    assert cr.channel_block(d, slots, K) == d
    _check(*_case([0, 7, 2, 5, 3, 1, 4, 10][:slots], d=d, stored="bfloat16"))


def test_the_grid_walks_blocks_of_channels(monkeypatch):
    """With room for one unit of channels a grid step, 2048 channels are
    two blocks, each with every slot; the block follows from the shapes
    and the VMEM the kernel may hold."""
    room = 2 * 4 * 6 * (2 * K + 2) * cr.CHANNEL_UNIT
    monkeypatch.setattr(cr, "VMEM_BLOCKS", room)
    assert cr.channel_block(2048, 6, K) == 1024
    _check(*_case(POSITIONS["mixed"], d=2048))


def test_the_published_shape_is_blocks_of_1024_channels_with_every_slot():
    """5120 channels, 64 slots, 4 taps: five grid steps of ``[4, 64,
    1024]`` float32 (1 MB in, 1 MB out), not 64 steps of one slot."""
    assert cr.channel_block(5120, 64, 4) == 1024
    # a width the unit does not divide is one block
    assert cr.channel_block(5120 + 128, 64, 4) == 5120 + 128


def test_a_ring_wider_than_the_inner_width_is_one_block():
    """Mamba-2 convolves ``x | B | C``: the ring is ``d_inner + 2 d_state``
    channels wide (8448 at the published sizes, 8192 + 2 x 128), which the
    unit of 1024 channels does not divide: one block holds the layer's ring
    whole, every slot in it. At a small size of the same form (1024 + 2 x
    128 channels) the step is the twin's and the whole sequence's."""
    assert cr.channel_block(8192 + 2 * 128, 32, 4) == 8448
    d = 1024 + 2 * 128
    assert cr.channel_block(d, 6, K) == d
    _check(*_case(POSITIONS["mixed"], d=d, stored="bfloat16"))


def test_the_kernel_is_named_apart_from_the_recurrences_kernels():
    """perfbench matches ``^selective_scan`` and
    ``^selective_state_update`` in a device trace."""
    ss = importlib.import_module("triton_dist_tpu.ops.selective_scan")
    assert cr.CONV_KERNEL == "conv_ring_step"
    assert not cr.CONV_KERNEL.startswith((ss.SCAN_KERNEL, ss.UPDATE_KERNEL))


# -- a kernel called once a layer (ops/per_layer.py) --------------------------------

def _lowered(fn, *args):
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def test_a_programs_layers_trace_the_kernel_once():
    """Three layers' calls in one program, at a width no other test uses:
    the kernel's body is traced ONCE (the layer is a prefetched scalar,
    not a constant of the index maps), and the layers' results are those
    of the twin called a layer at a time."""
    (pool, u, *rest), _ = _case(POSITIONS["mixed"], d=384)
    traces = []
    body = cr._conv_kernel

    def counted(*refs):
        traces.append(1)
        return body(*refs)

    def three(step, pool, u):
        for li in range(LAYERS):
            u, pool = step(pool, li, u, *rest)
        return u, pool

    cr._conv_kernel = counted
    try:
        got = jax.jit(functools.partial(three, functools.partial(
            cr.conv_ring_step, interpret=True)))(pool, u)
    finally:
        cr._conv_kernel = body
    assert len(traces) == 1
    for g, w in zip(got, three(cr._xla_conv_ring_step, pool, u)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6,
                                   atol=1e-6)


def test_the_callers_scopes_are_reopened_inside_the_jitted_call():
    from triton_dist_tpu.obs.scopes import scope

    (pool, *rest), _ = _case(POSITIONS["mixed"], d=128)

    def step(pool, *rest):
        with scope("ssm"), scope("ssm/conv"):
            return cr.conv_ring_step(pool, LAYER, *rest, interpret=True)

    names = set(re.findall(r'loc\("([^"]+)"', _lowered(step, pool, *rest)))
    assert any("tdt.ssm/conv/" in n and "pallas_call" in n for n in names)
    assert not [n for n in names
                if "pallas_call" in n and "tdt.ssm/conv" not in n]


def test_an_armed_run_traces_every_call():
    """The watchdog's books are kept while ``dist_pallas_call`` traces: a
    cached trace would skip them."""
    from triton_dist_tpu import config as tdt_config
    from triton_dist_tpu.ops.per_layer import traced_once

    seen = []

    @traced_once
    def host(x, *, interpret):
        seen.append(interpret)
        return x + 1

    x = jnp.zeros((4,))
    run = jax.jit(lambda x: host(host(x, interpret=False), interpret=False))
    run(x)
    assert len(seen) == 1
    was = tdt_config.get_config().timeout_iters
    tdt_config.update(timeout_iters=1000)
    try:
        del seen[:]
        jax.jit(lambda x: host(host(x, interpret=False), interpret=False))(x)
        assert len(seen) == 2
    finally:
        tdt_config.update(timeout_iters=was)
