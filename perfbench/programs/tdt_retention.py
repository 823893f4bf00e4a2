"""The adapter for the power-retention family
(``triton_dist_tpu.models.retention``) through the SAME serving path as
the other adapters: ``ServingEngine`` over the paged ``ContinuousBatcher``
(cache kind ``state``: a matrix state a slot and no page), with the
batcher's own default of lookahead. A configuration names this adapter
under ``"program"``; the harness sees only :class:`System`.

What it knows of the program: how to build a ``RetentionConfig`` from the
configuration's published keys, and the layout the program stores weights
in: q, k and v as one kv-group-major ``wqkv``, gate and up through the
program's own ``pack_gate_up``; every other leaf as the reference makes it.
The reference's plain weights are packed into it here, on the device,
inside the program that makes them: no second copy of any leaf. Requests,
warm-up, program names, re-seeding and the dropping of the weights when the
window closes are ``tdt_mla_moe``'s, inherited; an admission runs ONE
slot's rows, so ``prefill_rows`` is the prompt's own bucket. The reference
gets the model's own keys from here (``reference.configure(config)``): the
harness hands it the sizes only.
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


def model_config(config: dict, interpret=None):
    """The program's model config from a configuration file."""
    from triton_dist_tpu.models.retention import RetentionConfig

    s = config["sizes"]
    if config.get("attention_bias") or config.get("tie_word_embeddings"):
        raise ValueError("the program has no bias on q, k, v, o and an "
                         "untied head")
    return RetentionConfig(
        vocab=s["vocab"], hidden=s["hidden"], ffn=s["ffn"],
        n_layers=s["n_layers"], n_q_heads=s["n_q_heads"],
        n_kv_heads=s["n_kv_heads"], head_dim=s["head_dim"],
        batch=config["engine"]["slots"], seq=8, rope_theta=s["rope_theta"],
        norm_eps=s["norm_eps"], dtype=jnp.dtype(s["dtype"]),
        interpret=interpret, power=config["power"],
    )


def pack_layer(w: dict, cfg) -> dict:
    """A layer's plain weights (``reference.layer_weights``) -> the
    program's layout."""
    from triton_dist_tpu.models.tp_transformer import pack_gate_up
    from triton_dist_tpu.models.window_moe import pack_qkv

    out = {k: v for k, v in w.items()
           if k not in ("w_gate", "w_up", "wq", "wk", "wv")}
    out["w_gate_up"] = pack_gate_up(w["w_gate"], w["w_up"], cfg)
    out["wqkv"] = pack_qkv(w["wq"], w["wk"], w["wv"], cfg)
    return out


class System(_moe.System):
    """``tdt_mla_moe.System`` with this family's config and packing."""

    def __init__(self, config: dict, reference, devices, seed: int):
        from triton_dist_tpu import config as tdt_config
        from triton_dist_tpu.models.retention import layer_plan
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
        # every layer is one kind: one generator
        self._plan = layer_plan(cfg)
        self._gen_layer = {"retention": jax.jit(
            lambda key, li: pack_layer(
                reference.layer_weights(key, li, self.sizes), cfg),
            out_shardings=to_sharding(specs["layers"][0]))}
        self._gen_outer = jax.jit(
            lambda key: reference.outer_weights(key, self.sizes),
            out_shardings=to_sharding(
                {k: specs[k] for k in ("embed", "final_norm", "lm_head")}))
        self.params = self._weights(seed)
        self.engine = ServingEngine(
            cfg, self.params, self.mesh, s_max=eng["s_max"],
            page_size=eng["page"], prefill=True,
            serving=ServingConfig(max_queue=eng["max_queue"]),
        )

    def prefill_rows(self, reqs) -> dict:
        """Rows each request's admission runs through the prefill program:
        its own bucket, one slot's rows."""
        bucket = self.engine._batcher._bucket
        return {r.uid: bucket(len(r.prompt)) for r in reqs}
