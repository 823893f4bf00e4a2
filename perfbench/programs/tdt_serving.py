"""The one place that touches the program: ``triton_dist_tpu``'s
``ServingEngine`` over the paged ``ContinuousBatcher``. A configuration
names this adapter under ``"program"``; the harness sees only
:class:`System`.

What it knows of the program: how to build a ``TransformerConfig`` from
the configuration's sizes, the layout its kernels want the weights in
(the reference's plain weights are packed into it here, on the device),
how requests go in (``ServingEngine.serve`` of ``Arrival``s on the
engine's clock) and what comes back (``Finished``), which prompt lengths
share a compiled program (``ContinuousBatcher._bucket``), and the names
its programs carry in a device trace.
"""

from __future__ import annotations

import functools
import gc

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from harness.stats import Record
from harness.traffic import Req

# names in the device trace's "XLA Modules" line (jit_<function name>)
PROGRAMS = {"decode_step": r"^jit_decode_step\b", "prefill": r"^jit_fn\b"}
# host-side methods wrapped in spans during a traced run, outermost first
SPANS = (("engine", "_step_once"), ("batcher", "_admit"),
         ("batcher", "_admit_prefill"), ("batcher", "_decode_round"))
WARM_TOKENS = 3


def pack_layer(w: dict, sizes: dict) -> dict:
    """Plain weights -> the program's layout: QKV kv-group-major
    ``[H, n_kv, (g q heads | k | v) * d]``, gate and up interleaved per
    ffn unit ``[H, F, 2]``; ``wo`` rows are already in that head order."""
    h, d, n_kv = sizes["hidden"], sizes["head_dim"], sizes["n_kv_heads"]
    wqkv = jnp.concatenate([
        w["wq"].reshape(h, n_kv, -1), w["wk"].reshape(h, n_kv, d),
        w["wv"].reshape(h, n_kv, d),
    ], axis=-1)
    return dict(
        attn_norm=w["attn_norm"], wqkv=wqkv, wo=w["wo"],
        mlp_norm=w["mlp_norm"],
        w_gate_up=jnp.stack([w["w_gate"], w["w_up"]], axis=-1),
        w_down=w["w_down"],
    )


def transformer_config(config: dict, interpret=None):
    """The program's model config from a configuration file's sizes."""
    from triton_dist_tpu.models.tp_transformer import TransformerConfig

    s = config["sizes"]
    return TransformerConfig(
        vocab=s["vocab"], hidden=s["hidden"], ffn=s["ffn"],
        n_layers=s["n_layers"], n_q_heads=s["n_q_heads"],
        n_kv_heads=s["n_kv_heads"], head_dim=s["head_dim"],
        batch=config["engine"]["slots"], seq=8, rope_theta=s["rope_theta"],
        norm_eps=s["norm_eps"], dtype=jnp.dtype(s["dtype"]),
        interpret=interpret,
    )


class System:
    def __init__(self, config: dict, reference, devices, seed: int):
        from triton_dist_tpu import config as tdt_config
        from triton_dist_tpu.models.tp_transformer import param_specs
        from triton_dist_tpu.serving import ServingConfig, ServingEngine

        # loud: a fused kernel that cannot build ends the run, no XLA twin
        tdt_config.update(fallback_to_xla=False)
        self.cache_dir = tdt_config.compile_cache_dir()
        self.config, self.sizes = config, config["sizes"]
        self.reference = reference
        eng = config["engine"]
        s = self.sizes
        self.cfg = transformer_config(config)
        self.devices = list(devices)
        self.mesh = Mesh(np.array(self.devices), (self.cfg.axis,))
        specs = param_specs(self.cfg)
        to_sharding = functools.partial(
            jax.tree.map, lambda p: NamedSharding(self.mesh, p),
            is_leaf=lambda p: isinstance(p, P))
        self._gen_layer = jax.jit(
            lambda key, li: pack_layer(
                reference.layer_weights(key, li, s), s),
            out_shardings=to_sharding(specs["layers"][0]))
        self._gen_outer = jax.jit(
            lambda key: reference.outer_weights(key, s),
            out_shardings=to_sharding(
                {k: specs[k] for k in ("embed", "final_norm", "lm_head")}))
        self.params = self._weights(seed)
        self.engine = ServingEngine(
            self.cfg, self.params, self.mesh, s_max=eng["s_max"],
            page_size=eng["page"], prefill=True,
            serving=ServingConfig(max_queue=eng["max_queue"]),
        )

    def _weights(self, seed: int) -> dict:
        key = self.reference.seed_key(seed)
        layers = [self._gen_layer(key, jnp.int32(li))
                  for li in range(self.sizes["n_layers"])]
        return jax.block_until_ready(dict(self._gen_outer(key), layers=layers))

    def reseed(self, seed: int) -> None:
        """Other weights under the same compiled engine (calibration over
        many seeds in one process)."""
        self.params = self.engine.params = self._weights(seed)
        self.engine._batcher.params = self.params

    @property
    def vocab(self) -> int:
        return self.sizes["vocab"]

    def weight_bytes_per_device(self) -> int:
        """Bytes of layer weights and head on the fullest device."""
        per = {}
        for leaf in jax.tree.leaves(
                dict(layers=self.params["layers"], h=self.params["lm_head"])):
            for sh in leaf.addressable_shards:
                per[sh.device.id] = per.get(sh.device.id, 0) + sh.data.nbytes
        return max(per.values())

    # -- traffic in, records out -----------------------------------------

    def _serve(self, reqs: list[Req], span: str | None) -> tuple[list, float]:
        from triton_dist_tpu.models.decode import Request
        from triton_dist_tpu.serving.engine import Finished
        from triton_dist_tpu.serving.traffic import Arrival

        clock = self.engine.clock
        t_open = clock.monotonic() + 0.02
        arrivals = [
            Arrival(t_open + r.t_s, Request(
                list(r.prompt), r.n_out, temperature=r.temperature,
                uid=r.uid))
            for r in reqs
        ]
        if span is None:
            results = self.engine.serve(arrivals)
        else:
            with jax.profiler.TraceAnnotation(span):
                results = self.engine.serve(arrivals)
        jax.block_until_ready(self.engine._batcher.cache)
        records = []
        for r in reqs:
            fin = results.get(r.uid)
            good = isinstance(fin, Finished)
            records.append(Record(
                uid=r.uid, n_prompt=len(r.prompt), n_wanted=r.n_out,
                tokens=tuple(int(t) for t in fin.tokens) if good else (),
                t_due=t_open + r.t_s,
                t_admitted=fin.t_admitted if good else None,
                t_first=fin.t_first_token if good else None,
                t_finished=fin.t_finished if good else None,
            ))
        return records, t_open

    def warm(self, reqs: list[Req]) -> list[int]:
        """Run every program the window will use, through the engine the
        window will drive: one admission per prompt-length bucket of the
        traffic, spread over every slot, and a few decode steps."""
        bucket = self.engine._batcher._bucket
        longest: dict[int, int] = {}
        for r in reqs:
            b = bucket(len(r.prompt))
            longest[b] = max(longest.get(b, 0), len(r.prompt))
        rng = np.random.default_rng(0)
        lens = sorted(longest.values())
        slots = self.config["engine"]["slots"]
        warm = [
            Req(f"warm{i}", 0.0,
                tuple(int(t) for t in rng.integers(0, self.vocab, lens[i % len(lens)])),
                WARM_TOKENS)
            for i in range(max(slots, len(lens)))
        ]
        records, _ = self._serve(warm, None)
        bad = [r.uid for r in records if not r.ok]
        if bad:
            raise RuntimeError(f"warm-up requests did not finish: {bad}")
        return sorted(longest)

    def prefill_rows(self, reqs: list[Req]) -> dict:
        """Rows each request's admission runs through the prefill program:
        the whole batch of slots times its prompt's bucket."""
        bucket = self.engine._batcher._bucket
        slots = self.config["engine"]["slots"]
        return {r.uid: slots * bucket(len(r.prompt)) for r in reqs}

    def serve(self, reqs: list[Req]) -> tuple[list, float]:
        """The measured window: ``(records, time it opened)``."""
        return self._serve(reqs, "perfbench.window")

    def annotate(self) -> None:
        """Traced runs only: host spans around the program's scheduling
        calls, put on from outside, so that a device gap can be given to
        what the host was doing."""
        owners = {"engine": self.engine, "batcher": self.engine._batcher}
        for owner, name in SPANS:
            fn = getattr(owners[owner], name, None)
            if fn is None:
                continue

            def spanned(*a, _fn=fn, _label=f"perfbench.{owner}.{name}", **kw):
                with jax.profiler.TraceAnnotation(_label):
                    return _fn(*a, **kw)

            setattr(owners[owner], name, spanned)

    def health_flips(self) -> int:
        """Downgrades, timeouts and the like that the program recorded:
        a fused kernel served by its XLA twin would show here."""
        from triton_dist_tpu.resilience import health

        snap = health.snapshot()
        flips = sum(
            n for k, n in snap["counters"].items()
            if n and k.rsplit(":", 1)[-1] in health.FLIP_KINDS)
        return int(flips) + (0 if snap["healthy"] else 1)

    def free(self) -> None:
        """Drop everything the program holds on the device."""
        self.engine = self.params = None
        gc.collect()
