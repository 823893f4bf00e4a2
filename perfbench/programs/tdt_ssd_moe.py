"""The adapter for the state-space / attention family's Mamba-2 + expert
plan (``triton_dist_tpu.models.ssm_hybrid`` with ``mamba2`` mixers and the
``experts`` MLP kind: the Granite-4.0-H block) through the SAME serving path
as the other adapters: ``ServingEngine`` over the paged ``ContinuousBatcher``
(cache kind ``kv_state``), with the batcher's own default of lookahead. A
configuration names this adapter under ``"program"``; the harness sees
only :class:`System`.

What it knows of the program: how to build an ``SSMHybridConfig`` from the
configuration's published keys (the published ``layer_types`` says
``mamba`` for what the program calls ``mamba2``: this model type's state-
space layer), and the layout the program stores weights in: q, k and v as
one kv-group-major ``wqkv``, the convolution's taps with the channels LAST
(``[K, d + 2N]``), the shared expert's and each routed expert's gate | up
as contiguous halves. The reference's plain weights are packed into it
here, on the device, inside the program that makes them, the bank
``EXPERT_CHUNK`` experts at a time: no second copy of any leaf. The head
is the embedding: there is no ``lm_head`` leaf. Requests, warm-up, program
names, re-seeding and the dropping of the weights when the window closes
are ``tdt_mla_moe``'s, inherited; an admission runs ONE slot's rows, so
``prefill_rows`` is the prompt's own bucket. The reference gets the model's
own keys from here (``reference.configure(config)``): the harness hands it
the sizes only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from harness import cells

_moe = cells.load_module("programs", "tdt_mla_moe")

# names in the device trace's "XLA Modules" line (jit_<function name>)
PROGRAMS = _moe.PROGRAMS
# experts of a bank made at once (the largest divisor of the held count
# that is at most this)
EXPERT_CHUNK = 8
# the published layer kind -> the program's mixer kind
MIXER_OF = {"mamba": "mamba2", "attention": "attention"}


def model_config(config: dict, interpret=None):
    """The program's model config from a configuration file."""
    from triton_dist_tpu.models.ssm_hybrid import SSMHybridConfig

    s = config["sizes"]
    if config["mamba_n_groups"] != 1 or config["mamba_proj_bias"] \
            or not config["mamba_conv_bias"] or config["attention_bias"] \
            or not config["tie_word_embeddings"] \
            or config["position_embedding_type"] != "nope":
        raise ValueError(
            "the program has one group of B and C, a bias on the "
            "convolution and on no projection, a tied head and no "
            "positional term")
    d_inner = config["mamba_expand"] * s["hidden"]
    if config["mamba_n_heads"] * config["mamba_d_head"] != d_inner:
        raise ValueError("mamba_n_heads x mamba_d_head is not the inner width")
    fe, fs = config["intermediate_size"], config["shared_intermediate_size"]
    if fs % fe:
        raise ValueError(f"the shared expert ({fs}) is not whole experts of {fe}")
    held, rows = config.get("experts_held"), config.get("vocab_held")
    return SSMHybridConfig(
        vocab=s["vocab"], hidden=s["hidden"], ffn=s["ffn"],
        n_layers=s["n_layers"], n_q_heads=s["n_q_heads"],
        n_kv_heads=s["n_kv_heads"], head_dim=s["head_dim"],
        batch=config["engine"]["slots"], seq=8, norm_eps=s["norm_eps"],
        dtype=jnp.dtype(s["dtype"]), interpret=interpret,
        layer_types=tuple(MIXER_OF[k]
                          for k in config["layer_types"][:s["n_layers"]]),
        d_inner=d_inner, d_state=config["mamba_d_state"],
        d_conv=config["mamba_d_conv"], ssm_heads=config["mamba_n_heads"],
        ssm_chunk=config["mamba_chunk_size"],
        n_experts=(config.get("published") or config)["num_local_experts"],
        topk=config["num_experts_per_tok"], expert_ffn=fe,
        n_shared_experts=fs // fe,
        experts_held=tuple(held) if held else None,
        vocab_held=tuple(rows) if rows else None,
        embedding_multiplier=config["embedding_multiplier"],
        residual_multiplier=config["residual_multiplier"],
        attention_multiplier=config["attention_multiplier"],
        logits_scaling=config["logits_scaling"],
    )


def pack_core(w: dict, cfg) -> dict:
    """A layer's plain weights (all but the bank) -> the program's
    layout."""
    from triton_dist_tpu.models.window_moe import pack_qkv

    gone = ("wq", "wk", "wv", "conv_w", "ws_gate", "ws_up")
    out = {k: v for k, v in w.items()
           if k not in gone and not k.startswith("we_")}
    out["ws_gate_up"] = jnp.concatenate([w["ws_gate"], w["ws_up"]], -1)
    if "wq" in w:
        out["wqkv"] = pack_qkv(w["wq"], w["wk"], w["wv"], cfg)
    else:
        out["conv_w"] = w["conv_w"].T
    return out


pack_experts = _moe.pack_experts


def pack_layer(w: dict, cfg) -> dict:
    """A whole plain layer (``reference.layer_weights``) packed (tests)."""
    return dict(pack_core(w, cfg), **pack_experts(w))


class System(_moe.System):
    """``tdt_mla_moe.System`` with this plan's config and packing."""

    def __init__(self, config: dict, reference, devices, seed: int):
        from triton_dist_tpu import config as tdt_config
        from triton_dist_tpu.models.ssm_hybrid import layer_plan
        from triton_dist_tpu.serving import ServingConfig, ServingEngine

        tdt_config.update(fallback_to_xla=False)
        self.cache_dir = tdt_config.compile_cache_dir()
        self.config, self.sizes = config, config["sizes"]
        self.reference = reference
        reference.configure(config)
        eng = config["engine"]
        self.cfg = cfg = model_config(config)
        self.devices = list(devices)
        self.mesh = Mesh(np.array(self.devices), (cfg.axis,))
        specs = cfg.param_specs()
        to_sharding = functools.partial(
            jax.tree.map, lambda p: NamedSharding(self.mesh, p),
            is_leaf=lambda p: isinstance(p, P))
        # weights differ by the mixer kind only: one generator a kind
        self._plan = layer_plan(cfg)
        self._gen_layer = {
            kind: jax.jit(
                functools.partial(self._layer, attention=kind == "attention"),
                out_shardings=to_sharding(
                    specs["layers"][self._plan.index(kind)]))
            for kind in set(self._plan)}
        self._gen_outer = jax.jit(
            lambda key: reference.outer_weights(key, self.sizes),
            out_shardings=to_sharding(
                {k: specs[k] for k in ("embed", "final_norm")}))
        self.params = self._weights(seed)
        self.engine = ServingEngine(
            cfg, self.params, self.mesh, s_max=eng["s_max"],
            page_size=eng["page"], prefill=True,
            serving=ServingConfig(max_queue=eng["max_queue"]),
        )

    def _layer(self, key, li, attention: bool) -> dict:
        """Layer ``li`` in the program's layout, the bank made and packed
        some experts at a time into its final place."""
        ref, s, cfg = self.reference, self.sizes, self.cfg
        w = pack_core(ref.core_weights(key, li, s, attention), cfg)
        first, count = cfg.held
        n = max(i for i in range(1, EXPERT_CHUNK + 1) if count % i == 0)
        banks = jax.lax.map(
            lambda e0: pack_experts(ref.expert_weights(key, li, e0, n, s)),
            first + jnp.arange(count // n, dtype=jnp.int32) * n)
        w.update({k: v.reshape(count, *v.shape[2:]) for k, v in banks.items()})
        return w

    def weight_bytes_per_device(self) -> int:
        """Bytes of the layers and of the embedding, which is the head."""
        return sum(leaf.nbytes for leaf in jax.tree.leaves(
            dict(layers=self.params["layers"], h=self.params["embed"])))

    def prefill_rows(self, reqs) -> dict:
        """Rows each request's admission runs through the prefill program:
        its own bucket, one slot's rows."""
        bucket = self.engine._batcher._bucket
        return {r.uid: bucket(len(r.prompt)) for r in reqs}
