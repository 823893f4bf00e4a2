"""The adapter for the state-space / attention family
(``triton_dist_tpu.models.ssm_hybrid``) through the SAME serving path as
the other adapters: ``ServingEngine`` over the paged ``ContinuousBatcher``
(cache kind ``kv_state``), with the batcher's own default of lookahead. A
configuration names this adapter under ``"program"``; the harness sees
only :class:`System`.

What it knows of the program: how to build an ``SSMHybridConfig`` from the
configuration's published keys, and the layout the program stores weights
in: q, k and v as one kv-group-major ``wqkv``, gate and up through the
program's own ``pack_gate_up``, the convolution's taps and ``A_log`` with
the channels LAST (``[K, d]``, ``[N, d]``: the kernels hold a state
``[N, d]``). The reference's plain weights are packed into it here, on the
device, inside the program that makes them: no second copy of any leaf.
The head is the embedding: there is no ``lm_head`` leaf. Requests, warm-up,
program names, re-seeding and the dropping of the weights when the window
closes are ``tdt_mla_moe``'s, inherited; an admission runs ONE slot's
rows, so ``prefill_rows`` is the prompt's own bucket. The reference gets
the model's own keys from here (``reference.configure(config)``): the
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
    from triton_dist_tpu.models.ssm_hybrid import SSMHybridConfig

    s = config["sizes"]
    if s["rope_theta"] is not None:
        raise ValueError("the family does not rotate: rope_theta is null")
    if config["num_experts"] != 1 or config["mamba_proj_bias"] \
            or not config["mamba_conv_bias"] or not config["tie_word_embeddings"]:
        raise ValueError(
            "the program has a plain MLP in every layer, a bias on the "
            "convolution and on no projection, and a tied head")
    return SSMHybridConfig(
        vocab=s["vocab"], hidden=s["hidden"], ffn=s["ffn"],
        n_layers=s["n_layers"], n_q_heads=s["n_q_heads"],
        n_kv_heads=s["n_kv_heads"], head_dim=s["head_dim"],
        batch=config["engine"]["slots"], seq=8, norm_eps=s["norm_eps"],
        dtype=jnp.dtype(s["dtype"]), interpret=interpret,
        attn_layer_period=config["attn_layer_period"],
        attn_layer_offset=config["attn_layer_offset"],
        d_inner=config["mamba_expand"] * s["hidden"],
        d_state=config["mamba_d_state"], d_conv=config["mamba_d_conv"],
        dt_rank=config["mamba_dt_rank"],
    )


def pack_layer(w: dict, cfg) -> dict:
    """A layer's plain weights (``reference.layer_weights``) -> the
    program's layout."""
    from triton_dist_tpu.models.tp_transformer import pack_gate_up
    from triton_dist_tpu.models.window_moe import pack_qkv

    out = {k: v for k, v in w.items()
           if k not in ("w_gate", "w_up", "wq", "wk", "wv", "conv_w", "a_log")}
    out["w_gate_up"] = pack_gate_up(w["w_gate"], w["w_up"], cfg)
    if "wq" in w:
        out["wqkv"] = pack_qkv(w["wq"], w["wk"], w["wv"], cfg)
    else:
        out.update(conv_w=w["conv_w"].T, a_log=w["a_log"].T)
    return out


class System(_moe.System):
    """``tdt_mla_moe.System`` with this family's config and packing."""

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
        return pack_layer(
            self.reference.layer_weights(key, li, self.sizes, attention),
            self.cfg)

    def weight_bytes_per_device(self) -> int:
        """Bytes of the layers and of the embedding, which is the head."""
        return sum(leaf.nbytes for leaf in jax.tree.leaves(
            dict(layers=self.params["layers"], h=self.params["embed"])))

    def prefill_rows(self, reqs) -> dict:
        """Rows each request's admission runs through the prefill program:
        its own bucket, one slot's rows."""
        bucket = self.engine._batcher._bucket
        return {r.uid: bucket(len(r.prompt)) for r in reqs}
