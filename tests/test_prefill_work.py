"""Ranged prefill, the price of admission: chunked admission sweeps strictly
fewer query x key pairs than the bulk bucket rectangle (the batcher's
``prefill_work_total``) while staying in the token-fed byte-identity class,
and the engine bills exactly that on its clock (``virtual_prefill_work_s``).
One file: both cells run the same bulk prefill program (split from
test_ranged_batcher.py and test_ranged_engine.py)."""

import pytest

from ranged_helpers import _bt_run, _mk, _serve, bt_prompts, model1, tok_fed


def test_chunked_prefill_byte_identity(mesh2, model1, bt_prompts, tok_fed):
    """Chunked admission (prefill_chunk_tokens) vs token-fed vs bulk
    prefill: one byte-identity class — and the swept-work counter prices
    the chunk strips strictly below the bulk bucket rectangle."""
    p1, p2 = bt_prompts
    reqs = lambda: [_mk("a", p1), _mk("c", p2)]
    c_on, bt_on = _bt_run(
        model1, mesh2, reqs(), prefill=True, prefill_chunk_tokens=3
    )
    c_off, bt_off = _bt_run(model1, mesh2, reqs(), prefill=True)
    assert c_on == tok_fed == c_off
    # 8-token prompt: bulk = 8×8 rectangle; chunks (0,3)(3,6)(6,8) sweep
    # 4·3 + 4·6 + 2·8 = 52 pairs — chunking does strictly less work
    assert bt_on.prefill_work_total == 2 * 52
    assert bt_off.prefill_work_total == 2 * 64
    assert bt_on.prefill_tokens_total == bt_off.prefill_tokens_total == 16


def test_engine_prefill_work_charge(mesh2, model1, bt_prompts):
    """virtual_prefill_work_s prices the swept rectangle on the engine
    clock: the bulk arm charges bucket² pairs where the chunked arm
    charges its strips — strictly less virtual time for the same tokens
    — and a zero/None knob charges nothing (byte-identical clocks)."""
    from triton_dist_tpu.serving.engine import ServingConfig

    p1, _ = bt_prompts

    def elapsed(serving, **kw):
        eng = _serve(model1, mesh2, [_mk("a", p1)], serving=serving, **kw)
        return eng.clock.monotonic(), eng.results["a"].tokens

    t_bulk, tok_bulk = elapsed(
        ServingConfig(virtual_step_s=0.05, virtual_prefill_work_s=0.01),
        prefill=True,
    )
    t_chunk, tok_chunk = elapsed(
        ServingConfig(
            virtual_step_s=0.05, virtual_prefill_work_s=0.01,
            prefill_chunk_tokens=3,
        ),
        prefill=True,
    )
    t_free, tok_free = elapsed(
        ServingConfig(virtual_step_s=0.05), prefill=True
    )
    assert tok_bulk == tok_chunk == tok_free
    # bulk sweeps the 8×8 rectangle (0.64s); chunks sweep 52 pairs
    # (0.52s) but pay 2 extra parked steps (0.10s)
    assert t_bulk - t_free == pytest.approx(64 * 0.01)
    assert t_chunk == pytest.approx(t_free + 52 * 0.01 + 2 * 0.05)

    with pytest.raises(ValueError, match="virtual_prefill_work_s"):
        ServingConfig(virtual_prefill_work_s=-1.0).validate()
