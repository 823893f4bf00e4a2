"""Named model-shape presets — the reference's benchmark shape table as
ready-to-run configs (≙ the perf-test suite's shape list,
reference ``python/triton_dist/test/nvidia/test_ag_gemm.py:149-156``:
M=8192 with N/K drawn from LLaMA-7B / 3.1-8B / 3.1-70B / 3.1-405B,
Mistral-7B, Qwen2-72B; the MoE tests use 8 experts, top-2, at 4096 x
14336).

The dense numbers are the public architecture shapes of the open-weight
models. ``moe-gelu-8x`` is NOT a published model: its experts are the
one-projection gelu stand-in of ``MoETransformerConfig`` at the dense
``ffn`` width (8 experts, top-2, softmax routing), kept for the fused
AG-GroupGEMM / MoE-Reduce-RS pipelines and their tests. A published
gated-expert model (SwiGLU experts at their own width, shared expert,
sigmoid routing, latent attention) is ``models/mla_moe.MLAMoEConfig``.
Presets carry GLOBAL dimensions; sharding is derived by ``param_specs`` /
``moe_param_specs`` from the mesh, so the same preset runs at any TP
degree that divides its head/ffn counts (``validate_tp`` checks).

    cfg = presets.preset("llama-3.1-8b", batch=1, seq=8192)
    cfg = presets.preset("moe-gelu-8x", tp_check=8)
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp

from triton_dist_tpu.models.tp_transformer import (
    EPMoETransformerConfig,
    MoETransformerConfig,
    TransformerConfig,
)

# name → (hidden, ffn, n_q_heads, n_kv_heads, head_dim, vocab[, E, topk])
_DENSE = {
    "llama-7b": (4096, 11008, 32, 32, 128, 32000),
    "llama-3.1-8b": (4096, 14336, 32, 8, 128, 128256),
    "llama-3.1-70b": (8192, 28672, 64, 8, 128, 128256),
    "llama-3.1-405b": (16384, 53248, 128, 8, 128, 128256),
    "mistral-7b": (4096, 14336, 32, 8, 128, 32768),
    "qwen2-72b": (8192, 29568, 64, 8, 128, 152064),
}
_MOE = {
    "moe-gelu-8x": (4096, 14336, 32, 8, 128, 32000, 8, 2),
}

PRESETS = tuple(sorted((*_DENSE, *_MOE)))


def validate_tp(cfg: TransformerConfig, tp: int) -> None:
    """Raise if the preset's global shapes don't divide across `tp` PEs
    (kv heads bound attention TP; ffn bounds the MLP TP)."""
    if cfg.n_kv_heads % tp:
        raise ValueError(
            f"tp={tp} does not divide n_kv_heads={cfg.n_kv_heads}"
        )
    # the gelu stand-in's experts share the dense `ffn` (MoETransformerConfig
    # adds expert COUNT, not a distinct width: MLAMoEConfig.expert_ffn is
    # the gated expert's own), so one check covers both
    if cfg.ffn % tp:
        raise ValueError(f"tp={tp} does not divide ffn={cfg.ffn}")


def preset(
    name: str,
    *,
    batch: int = 1,
    seq: int = 8192,
    n_layers: int | None = None,
    dtype: Any = jnp.bfloat16,
    tp_check: int | None = None,
    ep: bool = False,
    ep_outer: str | None = None,
    **overrides: Any,
) -> TransformerConfig:
    """Build the named model's config. `n_layers` defaults to 1 (a single
    decoder block — the unit the reference's per-op benchmarks compose);
    pass the real depth for full-model runs. Extra keyword arguments
    override any config field (e.g. ``ag_config=...``).

    MoE presets additionally take the deployment: ``ep=True`` builds the
    EXPERT-parallel config (whole experts per PE, tokens over the a2a —
    the reference's serving deployment) instead of the tensor-parallel
    one; ``ep_outer="dcn"`` further selects the hierarchical two-phase
    dispatch over an (outer, inner) mesh (≙ the reference's multi-node
    EPAll2AllLayer). A name suffix spells the same thing for CLI
    callers: ``"moe-gelu-8x:ep"`` / ``"moe-gelu-8x:ep-hier"``."""
    if name.endswith(":ep-hier"):
        name, ep, ep_outer = name[: -len(":ep-hier")], True, ep_outer or "dcn"
    elif name.endswith(":ep"):
        name, ep = name[: -len(":ep")], True
    if name in _MOE:
        h, f, q, kv, d, vocab, n_exp, topk = _MOE[name]
        moe_cls = EPMoETransformerConfig if (ep or ep_outer) else (
            MoETransformerConfig
        )
        if ep_outer is not None:
            overrides = dict(overrides, ep_outer=ep_outer)
        cfg: TransformerConfig = moe_cls(
            vocab=vocab, hidden=h, ffn=f, n_layers=n_layers or 1,
            n_q_heads=q, n_kv_heads=kv, head_dim=d, batch=batch, seq=seq,
            dtype=dtype, n_experts=n_exp, topk=topk, **overrides,
        )
    elif name in _DENSE:
        if ep or ep_outer:
            raise ValueError(
                f"preset {name!r} is dense — expert parallelism applies "
                f"to MoE presets only ({sorted(_MOE)})"
            )
        h, f, q, kv, d, vocab = _DENSE[name]
        cfg = TransformerConfig(
            vocab=vocab, hidden=h, ffn=f, n_layers=n_layers or 1,
            n_q_heads=q, n_kv_heads=kv, head_dim=d, batch=batch, seq=seq,
            dtype=dtype, **overrides,
        )
    else:
        raise KeyError(f"unknown preset {name!r}; have {PRESETS}")
    if tp_check is not None:
        validate_tp(cfg, tp_check)
    return cfg

