"""Scopes in the device trace (docs/observability.md): the one table of
names (``triton_dist_tpu/obs/scopes.py``) and the dense family's passes,
beside ``test_serving_spans.py``'s spans. A pass is lowered, never
compiled or run (``scope_helpers``); the layer-plan families' cases live
with their toy models (``test_mla_moe`` / ``test_window_moe`` /
``test_ssm_hybrid``)."""

import re

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu.models import init_params
from triton_dist_tpu.models.decode import PagedKVCacheSpec
from triton_dist_tpu.models.tp_transformer import TransformerConfig
from triton_dist_tpu.obs import scopes
from triton_dist_tpu.ops.allgather_gemm import AGGemmConfig
from triton_dist_tpu.ops.gemm_reduce_scatter import GemmRSConfig

from scope_helpers import admission_names, check_scopes, step_names

# the dense family's row of the table
SCOPES = {"attn", "attn/qkv", "attn/kv_write", "attn/out",
          "ffn", "ffn/gate_up", "ffn/act", "ffn/down", "head"}
PAGE, BUCKET = 4, 8


def _dense(world: int):
    """``(cfg, parameter shapes, paged cache spec, mesh, s_max)`` of the
    tiny one-block model over ``world`` devices."""
    cfg = TransformerConfig(
        vocab=32, hidden=32, ffn=64, n_layers=1, n_q_heads=4,
        n_kv_heads=max(2, world), head_dim=8, batch=max(2, world), seq=BUCKET,
        ag_config=AGGemmConfig(8, 16, 16), rs_config=GemmRSConfig(8, 16, 16))
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    s_max = 16 * world
    return (cfg, params, PagedKVCacheSpec(s_max, PAGE, static_table=True),
            Mesh(np.array(jax.devices()[:world]), ("tp",)), s_max)


def _kernels_under(names: set) -> set:
    """``<part>/<sub-part>/<kernel>`` of every Pallas call of ``names``."""
    out = set()
    for name in names:
        segs = name.split("/")
        if segs[-1] == "pallas_call":
            at = next(i for i, s in enumerate(segs) if s.startswith("tdt."))
            out.add("/".join(segs[at:-1])[len(scopes.PREFIX):])
    return out


@pytest.mark.parametrize("which", ["step", "admission", "tp4 admission"])
def test_every_part_of_a_dense_pass_says_which_part_it_is(which):
    """The dense step, the one-chip admission and the TP=4 admission (the
    ``TPTransformer`` layers with their fused rings) carry every scope of
    the family's row and no other ``tdt.`` name; every matrix product and
    kernel call lies under a part, each ring under its projection."""
    cfg, params, spec, mesh, s_max = _dense(4 if which.startswith("tp4") else 1)
    if which == "step":
        names = step_names(cfg, params, spec, mesh)
        check_scopes(names, SCOPES | {"attn/decode"})
        assert {k.rsplit("/", 1)[0] for k in _kernels_under(names)} == {
            "attn/decode"}
        return
    names = admission_names(cfg, params, spec, mesh, s_max, BUCKET)
    check_scopes(names, SCOPES)
    # (one device: ``gemm_rs``; four: ``gemm_rs_scatter``; each with the
    # tile it ran, ``_8m16n16k``: ops.common.GemmTile.tag)
    found = _kernels_under(names)
    assert all(re.search(r"_\d+m\d+n\d+k$", k) for k in found), found
    assert {re.sub(r"(_scatter)?(_\d+m\d+n\d+k)+$", "", k) for k in found} == {
        "attn/qkv/ag_gemm", "attn/out/gemm_rs", "ffn/gate_up/ag_gemm",
        "ffn/down/gemm_rs", "head/ag_gemm"}


def test_the_helper_opens_names_of_the_table_and_refuses_any_other():
    assert set(scopes.PARTS) == {"attn", "ffn", "ssm", "retn", "head"}
    assert len(scopes.NAMES) == 5 + sum(map(len, scopes.PARTS.values()))

    @jax.jit
    def f(x):
        with scopes.scope("ffn"), scopes.scope("ffn/act"):
            return x * 2

    text = f.lower(1.0).as_text(debug_info=True)
    assert "tdt.ffn/act/mul" in text
    for name in ("mlp", "tdt.attn", "attn/act", "ffn/", "moe_experts"):
        with pytest.raises(ValueError, match="is not a scope"):
            scopes.scope(name)
