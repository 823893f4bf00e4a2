"""The adapter for the latent-attention / gated-expert family
(``triton_dist_tpu.models.mla_moe``) through the SAME serving path as
``tdt_serving``: ``ServingEngine`` over the paged ``ContinuousBatcher``.
A configuration names this adapter under ``"program"``; the harness sees
only :class:`System`.

What it knows of the program: how to build an ``MLAMoEConfig`` from the
configuration's published keys, the layout the program stores weights in
(the reference's plain weights are packed into it here, on the device, an
expert chunk at a time: an 11 GB tree leaves no room for a second copy of
any bank), and what ``tdt_serving`` knows about requests, buckets and
program names. The reference gets the model's own keys from here
(``reference.configure(config)``): the harness hands it the sizes only.
"""

from __future__ import annotations

import functools
import gc

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from harness import cells

_dense = cells.load_module("programs", "tdt_serving")

# names in the device trace's "XLA Modules" line (jit_<function name>)
PROGRAMS = _dense.PROGRAMS
EXPERT_CHUNK = 16


def model_config(config: dict, interpret=None):
    """The program's model config from a configuration file."""
    from triton_dist_tpu.models.mla_moe import MLAMoEConfig

    s = config["sizes"]
    held = config.get("experts_held")
    if config["scoring_func"] != "sigmoid":
        raise ValueError("the program's router scores with sigmoid, not "
                         f"{config['scoring_func']!r}")
    return MLAMoEConfig(
        vocab=s["vocab"], hidden=s["hidden"], ffn=s["ffn"],
        n_layers=s["n_layers"], n_q_heads=s["n_q_heads"],
        n_kv_heads=s["n_kv_heads"],
        # the published head_dim is the rotary width; attention's q/k
        # width is nope + rope
        head_dim=config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        batch=config["engine"]["slots"], seq=8, rope_theta=s["rope_theta"],
        norm_eps=s["norm_eps"], dtype=jnp.dtype(s["dtype"]),
        interpret=interpret,
        q_lora_rank=config["q_lora_rank"], kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        n_experts=config["n_routed_experts"],
        topk=config["num_experts_per_tok"],
        expert_ffn=config["moe_intermediate_size"],
        n_shared_experts=config["n_shared_experts"],
        first_k_dense=config["first_k_dense_replace"],
        routed_scaling=config["routed_scaling_factor"],
        experts_held=tuple(held) if held else None,
    )


def pack_core(w: dict, cfg) -> dict:
    """A layer's plain weights (all but the bank) -> the program's layout:
    ``W_kvb`` split into the key part (absorbed into the query at decode)
    and the value part, ``[rkv, heads, width]`` each; the dense layer's
    gate and up through the program's own ``pack_gate_up``; the shared
    expert's gate | up as contiguous halves."""
    from triton_dist_tpu.models.tp_transformer import pack_gate_up

    kvb = w["wkv_b"].reshape(cfg.kv_lora_rank, cfg.n_q_heads, -1)
    out = {k: w[k] for k in ("attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a",
                             "kv_norm", "wo", "mlp_norm")}
    out.update(wkv_b_k=kvb[..., : cfg.qk_nope_head_dim],
               wkv_b_v=kvb[..., cfg.qk_nope_head_dim:])
    if "w_gate" in w:
        out.update(w_gate_up=pack_gate_up(w["w_gate"], w["w_up"], cfg),
                   w_down=w["w_down"])
    else:
        out.update(
            router=w["router"], router_bias=w["router_bias"],
            ws_gate_up=jnp.concatenate([w["ws_gate"], w["ws_up"]], -1),
            ws_down=w["ws_down"])
    return out


def pack_experts(bank: dict) -> dict:
    """Plain expert weights ``[n, ...]`` -> gate | up contiguous halves."""
    return dict(
        we_gate_up=jnp.concatenate([bank["we_gate"], bank["we_up"]], -1),
        we_down=bank["we_down"])


def pack_layer(w: dict, cfg) -> dict:
    """A whole plain layer (``reference.layer_weights``) packed (tests)."""
    out = pack_core(w, cfg)
    if "we_gate" in w:
        out.update(pack_experts(w))
    return out


class System(_dense.System):
    """``tdt_serving.System`` with this family's config and weights; the
    serving calls, warm-up, records and health readout are inherited."""

    def __init__(self, config: dict, reference, devices, seed: int):
        from triton_dist_tpu import config as tdt_config
        from triton_dist_tpu.models.mla_moe import layer_plan
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
        self._plan = layer_plan(cfg)
        gens = {}
        for li, kind in enumerate(self._plan):
            if kind not in gens:
                gens[kind] = jax.jit(
                    functools.partial(self._layer, dense=kind == "dense"),
                    out_shardings=to_sharding(specs["layers"][li]))
        self._gen_layer = gens
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

    def _layer(self, key, li, dense: bool) -> dict:
        """Layer ``li`` in the program's layout, the bank made and packed
        ``EXPERT_CHUNK`` experts at a time into its final place."""
        ref, s, cfg = self.reference, self.sizes, self.cfg
        w = pack_core(ref.core_weights(key, li, s, dense), cfg)
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

    def _weights(self, seed: int) -> dict:
        key = self.reference.seed_key(seed)
        layers = [self._gen_layer[kind](key, jnp.int32(li))
                  for li, kind in enumerate(self._plan)]
        return jax.block_until_ready(dict(self._gen_outer(key), layers=layers))

    def reseed(self, seed: int) -> None:
        """Other weights under the same compiled engine: the old tree goes
        first, two do not fit."""
        self._drop_weights()
        self.params = self.engine.params = self._weights(seed)
        self.engine._batcher.params = self.params

    def _drop_weights(self) -> None:
        self.params = self.engine.params = None
        self.engine._batcher._params = None
        gc.collect()

    def serve(self, reqs):
        """The measured window; then the weights go (no caller uses the
        system's weights after its window: ``run.py`` frees it, and
        ``control.py`` reseeds it, and the reference that runs in between
        needs the room)."""
        out = super().serve(reqs)
        self._drop_weights()
        return out
