"""The adapter for the latent-attention / gated-expert family's SECOND
configuration kind (``triton_dist_tpu.models.mla_moe`` with a layer plan of
two attention kinds: full layers behind a learned indexer, window layers
with latent widths of their own, a headwise gate, the latent rescale; one
chip's share of the experts and of the vocabulary) through the SAME serving
path as the other adapters: ``ServingEngine`` over the paged
``ContinuousBatcher`` (cache kind ``latent``: latent rows of two widths on
two page lifetimes and the index keys' pool), lookahead where the
configuration says so. A configuration names this adapter under
``"program"``; the harness sees only :class:`System`.

What it knows of the program: how to build an ``MLAMoEConfig`` from the
configuration's published keys (the plain keys are the full layers'
geometry, the ``swa_*`` keys the window layers') and its share
(``experts_held``, ``vocab_held``), and the layout the program stores
weights in (``tdt_mla_moe.pack_core``'s, with the gate and the indexer's
leaves beside it). The reference's plain weights are packed here, on the
device, the bank ``EXPERT_CHUNK`` experts at a time into its final place.
Requests, buckets, program names, re-seeding and the dropping of the
weights when the window closes are ``tdt_mla_moe``'s, inherited. The
reference gets the model's own keys from here
(``reference.configure(config)``): the harness hands it the sizes only.
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
EXPERT_CHUNK = 8
KINDS = {"full_attention": "full", "sliding_attention": "window"}
INDEX_LEAVES = ("wi_q", "wi_k", "wi_k_norm", "wi_k_bias", "wi_w")


def model_config(config: dict, interpret=None):
    """The program's model config from a configuration file."""
    from triton_dist_tpu.models.mla_moe import MLAMoEConfig

    s = config["sizes"]
    if config["scoring_func"] != "sigmoid":
        raise ValueError("the program's router scores with sigmoid, not "
                         f"{config['scoring_func']!r}")
    if {config["attention_gate_type"],
            config["swa_attention_gate_type"]} != {"headwise"}:
        raise ValueError("the program gates attention headwise")
    if config["swa_num_key_value_heads"] != config["swa_num_attention_heads"]:
        raise ValueError("latent attention has one cached row for every head")
    held, vocab_held = config.get("experts_held"), config.get("vocab_held")
    return MLAMoEConfig(
        vocab=s["vocab"], hidden=s["hidden"], ffn=s["ffn"],
        n_layers=s["n_layers"], n_q_heads=s["n_q_heads"],
        n_kv_heads=s["n_kv_heads"],
        # the harness's head_dim is the rotary width; attention's q/k
        # width is nope + rope
        head_dim=config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        batch=config["engine"]["slots"], seq=8, rope_theta=s["rope_theta"],
        norm_eps=s["norm_eps"], dtype=jnp.dtype(s["dtype"]),
        interpret=interpret,
        q_lora_rank=config["q_lora_rank"], kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        # the router keeps its published width; the key counts the held
        n_experts=(config.get("published") or config)["n_routed_experts"],
        topk=config["num_experts_per_tok"],
        expert_ffn=config["moe_intermediate_size"],
        n_shared_experts=config["n_shared_experts"],
        first_k_dense=config["first_k_dense_replace"],
        routed_scaling=config["routed_scaling_factor"],
        experts_held=tuple(held) if held else None,
        vocab_held=tuple(vocab_held) if vocab_held else None,
        layer_types=tuple(
            KINDS[k] for k in config["layer_types"][: s["n_layers"]]),
        window=config["sliding_window_size"],
        swa_n_heads=config["swa_num_attention_heads"],
        swa_q_lora_rank=config["swa_q_lora_rank"],
        swa_kv_lora_rank=config["swa_kv_lora_rank"],
        swa_qk_nope_head_dim=config["swa_qk_nope_head_dim"],
        swa_qk_rope_head_dim=config["swa_qk_rope_head_dim"],
        swa_v_head_dim=config["swa_v_head_dim"],
        swa_rope_theta=float(config["swa_rope_theta"]),
        index_n_heads=config["index_n_heads"],
        index_head_dim=config["index_head_dim"],
        index_topk=config["index_topk"],
        attn_gate=True,
        lora_rescale=bool(config["apply_mla_qkv_lora_rescale"]),
    )


def pack_core(w: dict, cfg, kind: str) -> dict:
    """A layer's plain weights (all but the bank) -> the program's
    layout, in the geometry of its attention ``kind``: ``W_kvb`` split
    into the key part (absorbed into the query at decode) and the value
    part; the gate and the indexer's leaves as they are."""
    from triton_dist_tpu.models.tp_transformer import pack_gate_up

    g = cfg.geometry(kind)
    kvb = w["wkv_b"].reshape(g.kv_rank, g.n_heads, -1)
    out = {k: w[k] for k in ("attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a",
                             "kv_norm", "wo", "mlp_norm")}
    out.update(wkv_b_k=kvb[..., : g.nope], wkv_b_v=kvb[..., g.nope:],
               w_attn_gate=w["w_attn_gate"])
    if g.indexed:
        out.update({k: w[k] for k in INDEX_LEAVES})
    if "w_gate" in w:
        out.update(w_gate_up=pack_gate_up(w["w_gate"], w["w_up"], cfg),
                   w_down=w["w_down"])
    else:
        out.update(
            router=w["router"], router_bias=w["router_bias"],
            ws_gate_up=jnp.concatenate([w["ws_gate"], w["ws_up"]], -1),
            ws_down=w["ws_down"])
    return out


pack_experts = _moe.pack_experts


def pack_layer(w: dict, cfg, kind: str) -> dict:
    """A whole plain layer (``reference.layer_weights``) packed (tests)."""
    out = pack_core(w, cfg, kind)
    if "we_gate" in w:
        out.update(pack_experts(w))
    return out


class System(_moe.System):
    """``tdt_mla_moe.System`` with this configuration kind's config and
    packing: one weight generator a pair of layer kinds."""

    def __init__(self, config: dict, reference, devices, seed: int):
        from triton_dist_tpu import config as tdt_config
        from triton_dist_tpu.models.mla_moe import layer_kinds
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
        self._plan = layer_kinds(cfg)
        self._gen_layer = {
            kinds: jax.jit(
                functools.partial(self._layer, kinds=kinds),
                out_shardings=to_sharding(
                    specs["layers"][self._plan.index(kinds)]))
            for kinds in set(self._plan)}
        self._gen_outer = jax.jit(
            lambda key: reference.outer_weights(key, self.sizes),
            out_shardings=to_sharding(
                {k: specs[k] for k in ("embed", "final_norm", "lm_head")}))
        self.params = self._weights(seed)
        self.engine = ServingEngine(
            cfg, self.params, self.mesh, s_max=eng["s_max"],
            page_size=eng["page"], prefill=True,
            lookahead=bool(eng.get("lookahead", False)),
            serving=ServingConfig(max_queue=eng["max_queue"]),
        )

    def _layer(self, key, li, kinds: tuple) -> dict:
        """Layer ``li`` in the program's layout, the bank made and packed
        ``EXPERT_CHUNK`` experts at a time into its final place."""
        ref, s, cfg = self.reference, self.sizes, self.cfg
        kind, dense = kinds[0], kinds[1] == "dense"
        w = pack_core(ref.core_weights(key, li, s, kind, dense), cfg, kind)
        if dense:
            return w
        first, count = cfg.held
        n = min(EXPERT_CHUNK, count)
        if count % n:
            raise ValueError(f"{count} experts held: not whole chunks of {n}")
        banks = jax.lax.map(
            lambda e0: pack_experts(ref.expert_weights(key, li, e0, n, s)),
            first + jnp.arange(count // n, dtype=jnp.int32) * n)
        w.update({k: v.reshape(count, *v.shape[2:]) for k, v in banks.items()})
        return w

    def prefill_rows(self, reqs) -> dict:
        """Rows each request's admission runs through the prefill
        program: its own bucket (one slot's rows an admission)."""
        bucket = self.engine._batcher._bucket
        return {r.uid: bucket(len(r.prompt)) for r in reqs}
