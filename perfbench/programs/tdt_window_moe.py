"""The adapter for the window-attention / gated-expert family
(``triton_dist_tpu.models.window_moe``) through the SAME serving path as
the other adapters: ``ServingEngine`` over the paged ``ContinuousBatcher``
(cache kind ``kv_window``), with lookahead where the configuration says
so. A configuration names this adapter under ``"program"``; the harness
sees only :class:`System`.

What it knows of the program: how to build a ``WindowMoEConfig`` from the
configuration's published keys and its share (``experts_held``,
``vocab_held``), and the layout the program stores weights in: q, k and v
as one kv-group-major ``wqkv``, gate and up through the program's own
``pack_gate_up``, an expert's gate | up as contiguous halves. The
reference's plain weights are packed into it here, on the device, the
bank some experts at a time into its final place (no second copy of a
bank). Requests, buckets, program names, re-seeding and the dropping of
the weights when the window closes are ``tdt_mla_moe``'s, inherited. The
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
EXPERT_CHUNK = 4


def model_config(config: dict, interpret=None):
    """The program's model config from a configuration file."""
    from triton_dist_tpu.models.window_moe import WindowMoEConfig

    s = config["sizes"]
    if config["scoring_func"] != "sigmoid":
        raise ValueError("the program's router scores with sigmoid, not "
                         f"{config['scoring_func']!r}")
    held, vocab_held = config.get("experts_held"), config.get("vocab_held")
    windows = config["sliding_windows"][: s["n_layers"]]
    if any(w not in (0, config["sliding_window"]) for w in windows):
        raise ValueError("the program has one window width a model")
    return WindowMoEConfig(
        vocab=s["vocab"], hidden=s["hidden"], ffn=s["ffn"],
        n_layers=s["n_layers"], n_q_heads=s["n_q_heads"],
        n_kv_heads=s["n_kv_heads"], head_dim=s["head_dim"],
        batch=config["engine"]["slots"], seq=8, rope_theta=s["rope_theta"],
        norm_eps=s["norm_eps"], dtype=jnp.dtype(s["dtype"]),
        interpret=interpret,
        layer_types=tuple("window" if w else "full" for w in windows),
        window=config["sliding_window"],
        # the router keeps its published width; num_experts counts the held
        n_experts=(config.get("published") or config)["num_experts"],
        topk=config["num_experts_per_tok"],
        expert_ffn=config["moe_intermediate_size"],
        n_shared_experts=config["num_shared_experts"],
        first_k_dense=config["first_k_dense_replace"],
        routed_scaling=config["routed_scaling_factor"],
        experts_held=tuple(held) if held else None,
        vocab_held=tuple(vocab_held) if vocab_held else None,
    )


def pack_core(w: dict, cfg) -> dict:
    """A layer's plain weights (all but the bank) -> the program's layout."""
    from triton_dist_tpu.models.tp_transformer import pack_gate_up
    from triton_dist_tpu.models.window_moe import pack_qkv

    out = {k: w[k] for k in ("q_norm", "k_norm", "wo", "attn_norm",
                             "mlp_norm")}
    out["wqkv"] = pack_qkv(w["wq"], w["wk"], w["wv"], cfg)
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


def pack_layer(w: dict, cfg) -> dict:
    """A whole plain layer (``reference.layer_weights``) packed (tests)."""
    out = pack_core(w, cfg)
    if "we_gate" in w:
        out.update(pack_experts(w))
    return out


class System(_moe.System):
    """``tdt_mla_moe.System`` with this family's config and packing."""

    def __init__(self, config: dict, reference, devices, seed: int):
        from triton_dist_tpu import config as tdt_config
        from triton_dist_tpu.models.window_moe import layer_plan
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
        # weights differ by the MLP kind only: one generator a kind
        self._plan = tuple(mlp for _, mlp in layer_plan(cfg))
        self._gen_layer = {
            mlp: jax.jit(
                functools.partial(self._layer, dense=mlp == "dense"),
                out_shardings=to_sharding(
                    specs["layers"][self._plan.index(mlp)]))
            for mlp in set(self._plan)}
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
