"""Ranged prefill, chunked-scheduling tier (split from
test_ranged_prefill.py, see its docstring): chunked prefill composes with
the paged cache and the prefix cache, and interleaves with decode."""

import jax

import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu.models import init_params
from triton_dist_tpu.models.decode import ContinuousBatcher
from triton_dist_tpu.models.prefix_cache import PrefixCacheConfig
from ranged_helpers import (
    BT_SMAX, _bt_run, _mk, _model_cfg, bt_prompts, model1, tok_fed,
)


def test_chunked_composes_with_paged_and_px(
        mesh2, model1, bt_prompts, tok_fed):
    """Chunked admission over the paged cache, and chunked × prefix-cache
    together, stay in the byte-identity class."""
    p1, p2 = bt_prompts
    cp_on, _ = _bt_run(
        model1, mesh2, [_mk("a", p1)], prefill=True, prefill_chunk_tokens=3,
        page_size=4,
    )
    assert cp_on["a"] == tok_fed["a"]
    reqs = lambda: [_mk("a", p1), _mk("b", p1), _mk("c", p2)]
    o_pxt, _ = _bt_run(
        model1, mesh2, reqs(), page_size=4, prefix_cache=PrefixCacheConfig()
    )
    cpx_on, _ = _bt_run(
        model1, mesh2, reqs(), page_size=4,
        prefix_cache=PrefixCacheConfig(), prefill=True,
        prefill_chunk_tokens=2,
    )
    assert cpx_on == o_pxt


def test_chunked_interleaves_decode(mesh2, model1, bt_prompts, tok_fed):
    """A long prompt chunking at ct=2 while a neighbor slot decodes:
    the neighbor makes progress during the chunk steps (the scheduling
    point of the whole feature) and the long request's tokens still
    equal the token-fed reference."""
    cfg, params = model1
    p1, p2 = bt_prompts
    bt = ContinuousBatcher(
        cfg, params, mesh2, s_max=BT_SMAX, prefill=True,
        prefill_chunk_tokens=2,
    )
    # an answer long enough to still be decoding while "long" chunks
    bt.submit(_mk("short", p1[:2], new=6))
    bt.step()
    bt.submit(_mk("long", p1))
    neighbor_progress = []
    for _ in range(16):
        if bt.idle:
            break
        had_chunk = 1 in bt._chunk
        before = len(bt.slot_out[0]) if bt.slot_req[0] else None
        bt.step()
        after = len(bt.slot_out[0]) if bt.slot_req[0] else None
        if had_chunk and before is not None and after is not None:
            neighbor_progress.append(after > before)
    done = dict(bt.drain_finished())
    assert sorted(done) == ["long", "short"]
    assert done["long"] == tok_fed["a"]
    assert any(neighbor_progress), "neighbor never decoded during chunking"


def test_chunk_tokens_validation():
    """prefill_chunk_tokens is loud about nonsense postures."""
    cfg = _model_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    mesh = Mesh(np.array(jax.devices()[:4]), ("tp",))
    with pytest.raises(ValueError, match="prefill=True"):
        ContinuousBatcher(
            cfg, params, mesh, s_max=BT_SMAX, prefill_chunk_tokens=4
        )
    with pytest.raises(ValueError, match=">= 1"):
        ContinuousBatcher(
            cfg, params, mesh, s_max=BT_SMAX, prefill=True,
            prefill_chunk_tokens=0,
        )
