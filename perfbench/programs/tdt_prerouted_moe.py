"""The adapter for the window-attention / gated-expert family's SECOND
block (``triton_dist_tpu.models.window_moe``: SmallThinker's pre-norm
block with no q/k norm, a router that reads the layer's input, softmax
over the chosen logits, ReLU-gated experts, no shared expert, no dense
layer) through the SAME serving path as the other adapters:
``ServingEngine`` over the paged ``ContinuousBatcher`` (cache kind
``kv_window``, rings of ``ceil(window / page) + 1`` pages), lookahead
where the configuration says so. A configuration names this adapter under
``"program"``; the harness sees only :class:`System`.

What it knows of the program: how to build a ``WindowMoEConfig`` from the
configuration's published keys (the layer plan from
``sliding_window_layout``, which must equal ``rope_layout``; the block's
form from the family's published description, see the configuration's
``assumed``), and the layout the program stores weights in: q, k and v as
one kv-group-major ``wqkv``, an expert's gate | up as contiguous halves.
The reference's plain weights are packed into it here, on the device, the
bank ``EXPERT_CHUNK`` experts at a time into its final place (no second
copy of a bank). Requests, buckets, program names, re-seeding and the
dropping of the weights when the window closes are ``tdt_mla_moe``'s,
inherited. The reference gets the model's own keys from here
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


def model_config(config: dict, interpret=None):
    """The program's model config from a configuration file."""
    from triton_dist_tpu.models.window_moe import WindowMoEConfig

    s = config["sizes"]
    layout = config["sliding_window_layout"]
    if layout != config["rope_layout"]:
        raise ValueError("the program rotates a layer where it attends "
                         "through the window: rope_layout must equal "
                         "sliding_window_layout")
    if not (config["moe_primary_router_apply_softmax"]
            and config["norm_topk_prob"]):
        raise ValueError("the program's router takes the softmax over the "
                         "chosen logits")
    return WindowMoEConfig(
        vocab=s["vocab"], hidden=s["hidden"], ffn=s["ffn"],
        n_layers=s["n_layers"], n_q_heads=s["n_q_heads"],
        n_kv_heads=s["n_kv_heads"], head_dim=s["head_dim"],
        batch=config["engine"]["slots"], seq=8, rope_theta=s["rope_theta"],
        norm_eps=s["norm_eps"], dtype=jnp.dtype(s["dtype"]),
        interpret=interpret,
        layer_types=tuple("window" if w else "full"
                          for w in layout[: s["n_layers"]]),
        window=config["sliding_window_size"],
        n_experts=config["moe_num_primary_experts"],
        topk=config["moe_num_active_primary_experts"],
        expert_ffn=config["moe_ffn_hidden_size"],
        n_shared_experts=0, first_k_dense=0, routed_scaling=1.0,
        norm_placement="input", qk_norm=False, router_rows="layer_input",
        scoring="softmax", gate_act="relu",
    )


def pack_core(w: dict, cfg) -> dict:
    """A layer's plain weights (all but the bank) -> the program's layout."""
    from triton_dist_tpu.models.window_moe import pack_qkv

    out = {k: w[k] for k in ("wo", "attn_norm", "mlp_norm", "router")}
    out["wqkv"] = pack_qkv(w["wq"], w["wk"], w["wv"], cfg)
    return out


pack_experts = _moe.pack_experts


def pack_layer(w: dict, cfg) -> dict:
    """A whole plain layer (``reference.layer_weights``) packed (tests)."""
    return dict(pack_core(w, cfg), **pack_experts(w))


class System(_moe.System):
    """``tdt_mla_moe.System`` with this block's config and packing."""

    def __init__(self, config: dict, reference, devices, seed: int):
        from triton_dist_tpu import config as tdt_config
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
        # every layer holds the same tensors: one generator
        self._plan = ("moe",) * cfg.n_layers
        self._gen_layer = {"moe": jax.jit(
            self._layer, out_shardings=to_sharding(specs["layers"][0]))}
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

    def _layer(self, key, li) -> dict:
        """Layer ``li`` in the program's layout, the bank made and packed
        ``EXPERT_CHUNK`` experts at a time into its final place."""
        ref, s, cfg = self.reference, self.sizes, self.cfg
        w = pack_core(ref.core_weights(key, li, s), cfg)
        count = cfg.n_experts
        n = min(EXPERT_CHUNK, count)
        if count % n:
            raise ValueError(f"{count} experts: not whole chunks of {n}")
        banks = jax.lax.map(
            lambda e0: pack_experts(ref.expert_weights(key, li, e0, n, s)),
            jnp.arange(count // n, dtype=jnp.int32) * n)
        w.update({k: v.reshape(count, *v.shape[2:]) for k, v in banks.items()})
        return w

    def prefill_rows(self, reqs) -> dict:
        """Rows each request's admission runs through the prefill
        program: its own bucket (one slot's rows an admission)."""
        bucket = self.engine._batcher._bucket
        return {r.uid: bucket(len(r.prompt)) for r in reqs}
