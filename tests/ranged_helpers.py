"""Shared model, prompts and drivers of the ranged-prefill test files
(test_ranged_prefill / test_ranged_batcher / test_chunked_prefill /
test_ranged_engine — one file until PR 23 split it so six xdist workers
balance under ``--dist loadfile``).

What these files cost is interpreted steps, not compiles (a step on the
4-device mesh is seconds, a program's trace and compile a tenth of its
test): the model tier keeps two layers, because a one-layer cache holds
no bit that attention produced; the batcher and serving tiers compare
TOKENS, which one layer's attention already decides, on the smallest mesh
that has a second PE (conftest's ``mesh2``: a step there is a third of the
4-device step; the model tier keeps the mesh of four), and serve answers
just long enough to cross a decode round on the second PE."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding

from triton_dist_tpu.models import TransformerConfig, init_params
from triton_dist_tpu.models.decode import ContinuousBatcher, Request


from triton_dist_tpu.ops.allgather_gemm import AGGemmConfig


from triton_dist_tpu.ops.gemm_reduce_scatter import GemmRSConfig


B, L, S_MAX = 2, 8, 16


def _model_cfg(**over):
    base = dict(
        vocab=32, hidden=32, ffn=64, n_layers=2, n_q_heads=8, n_kv_heads=4,
        head_dim=8, batch=B, seq=L,
        ag_config=AGGemmConfig(8, 16, 16), rs_config=GemmRSConfig(8, 16, 16),
    )
    base.update(over)
    return TransformerConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = _model_cfg()
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def model1():
    """The batcher and serving tiers' model: one layer (see above)."""
    cfg = _model_cfg(n_layers=1)
    return cfg, init_params(jax.random.PRNGKey(2), cfg)


@pytest.fixture(scope="module")
def prompt():
    return jax.random.randint(
        jax.random.PRNGKey(1), (B, L), 0, 32, jnp.int32
    )


@pytest.fixture(scope="session")
def mesh1() -> Mesh:
    return Mesh(np.array(jax.devices()[:1]), ("tp",))


def _put(mesh, tree, specs):
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs
    )


# the cache is sharded by rows: 8 a PE on the batcher tiers' mesh of two
BT_SMAX = 16


@pytest.fixture(scope="module")
def bt_prompts():
    rng = np.random.default_rng(7)
    p1 = [int(x) for x in rng.integers(0, 32, 8)]
    p2 = p1[:6] + [int(x) for x in rng.integers(0, 32, 2)]  # shares page 0
    return p1, p2


def _bt_run(model, mesh, reqs, **kw):
    cfg, params = model
    bt = ContinuousBatcher(cfg, params, mesh, s_max=BT_SMAX, **kw)
    out = {}
    for r in reqs:
        bt.submit(r)
        out.update(dict(bt.run()))
    return out, bt


def _mk(uid, prompt, new=4, **kw):
    # 8-token prompts fill the first PE's rows: the answer's rows land on
    # the second
    return Request(list(prompt), max_new_tokens=new, uid=uid, **kw)


@pytest.fixture(scope="module")
def tok_fed(model1, mesh2, bt_prompts):
    """The token-fed contiguous batcher's answers to p1 ("a") and p2 ("c"):
    the reference of the batcher tier's byte-identity class, served once a
    file."""
    p1, p2 = bt_prompts
    return _bt_run(model1, mesh2, [_mk("a", p1), _mk("c", p2)])[0]


def _serve(model, mesh, reqs, serving=None, **kw):
    from triton_dist_tpu.resilience import retry
    from triton_dist_tpu.serving.engine import ServingConfig, ServingEngine

    cfg, params = model
    eng = ServingEngine(
        cfg, params, mesh, s_max=BT_SMAX, clock=retry.FakeClock(),
        serving=serving or ServingConfig(), **kw,
    )
    for r in reqs:
        eng.submit(r)
    eng.run_until_idle()
    return eng


