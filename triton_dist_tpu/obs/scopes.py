"""The names a device op carries for the part of the layer it belongs to.

A model's traced passes (``models/``: every family's ``decode_step`` and
admission) open ``jax.named_scope``s from ONE table, so that the HLO
``op_name`` of every instruction, and with it the device trace's event,
says which part of the layer it is: ``jit(decode_step)/.../tdt.attn/qkv/
dot_general``. A scope is metadata on the compiled program: always there,
free when no profiler runs, and it changes no instruction and no
instruction's name. The names are API like the ``tdt.*`` span names
(docs/observability.md, "Scopes in the device trace"): a reader outside
the package groups device time by them.

Five parts, a closed set; under each, sub-parts for the pieces of work a
metric or a ROADMAP item names. A part is a kind of work, not a layer:
sixteen layers' ops group under one name.
"""

from __future__ import annotations

import jax

PREFIX = "tdt."
PARTS: dict[str, tuple[str, ...]] = {
    # an attention layer whole: norm, projections and their gathers, rope,
    # the cache's page-table work, the k/v (latent, ring) write, the decode
    # kernel or an admission's scores-softmax-PV (under ``prefill`` where
    # the tiled kernel computes it: ops/flash_prefill.py), the
    # out-projection; a learned indexer's projections, scores and selection
    # (``index``) and the headwise gate on the attention's output (``gate``)
    "attn": ("qkv", "kv_write", "decode", "prefill", "out", "index", "gate"),
    # a feed-forward whole, dense or routed: norm, gate/up, activation,
    # down; the router with alignment, gather and combine, the two grouped
    # GEMMs with the activation between them, the shared expert
    "ffn": ("gate_up", "act", "down", "route", "experts", "shared"),
    # a state-space mixer whole: norm, in-projection, the convolution and
    # its ring, the dt / B / C projections, the recurrence kernel, the
    # gate and out-projection; the norm over the gated output where the
    # block has one (Mamba-2: a reduction over the whole inner width)
    "ssm": ("proj", "conv", "scan", "norm"),
    # a power-retention mixer whole: norm, the q/k/v projection with the
    # head norms and the rotation (``qkv``), the gate's projection and
    # log-sigmoid, the step's in-place state kernel (``update``) or an
    # admission's chunked kernel and the state's write (``prefill``), the
    # out-projection
    "retn": ("qkv", "gate", "update", "prefill", "out"),
    # the vocabulary's two ends: the embedding lookup; the final norm, the
    # head's GEMV and the logits' gather
    "head": (),
}
NAMES = frozenset(PARTS) | {
    f"{part}/{sub}" for part, subs in PARTS.items() for sub in subs}


def scope(name: str):
    """``scope("attn")`` opens ``tdt.attn``; ``scope("attn/qkv")`` opens
    ``qkv`` and is for use INSIDE ``scope("attn")``. A name the table does
    not hold is refused."""
    if name not in NAMES:
        raise ValueError(f"{name!r} is not a scope of {sorted(NAMES)}")
    part, _, sub = name.partition("/")
    return jax.named_scope(sub or PREFIX + part)
