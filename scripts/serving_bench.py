"""Serving throughput probe on a real chip: steady-state continuous-
batching decode at LLaMA-3.1-8B layer shapes (depth cut to fit a probe),
reported as tokens/second — practical-serving evidence to go with the
correctness goldens (tests/test_decode.py) and the per-op bench
(bench.py; this is intentionally NOT a driver metric — there is no
reference baseline to ratio against).

    python scripts/serving_bench.py [preset] [n_layers] [batch] [steps]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from triton_dist_tpu.models import init_params, presets
from triton_dist_tpu.models.decode import ContinuousBatcher, Request


def main() -> int:
    name = sys.argv[1] if len(sys.argv) > 1 else "llama-3.1-8b"
    n_layers = int(sys.argv[2]) if len(sys.argv) > 2 else 4
    batch = int(sys.argv[3]) if len(sys.argv) > 3 else 8
    steps = int(sys.argv[4]) if len(sys.argv) > 4 else 200
    interp = os.environ.get("TDT_SERVING_BENCH_INTERPRET") == "1"
    from triton_dist_tpu import config as tdt_config

    # loud: a kernel that cannot build fails the probe, never its golden
    tdt_config.update(fallback_to_xla=False)
    if interp:
        jax.config.update("jax_platforms", "cpu")
        n_layers, batch, steps = 1, 2, 8
    elif jax.default_backend() != "tpu":
        print(f"ERROR: no TPU (backend={jax.default_backend()})",
              file=sys.stderr)
        return 1

    import dataclasses

    s_max = 512 if not interp else 32
    cfg = presets.preset(
        name, batch=batch, seq=8, n_layers=n_layers,
    )
    cfg = dataclasses.replace(cfg, vocab=2048)  # probe: logit head only
    if interp:
        # plumbing-only mode: real-model dims take minutes/step on a CPU
        # interpreter — shrink everything, keep the preset's head ratios
        cfg = dataclasses.replace(
            cfg, hidden=64, ffn=128, n_q_heads=4, n_kv_heads=2,
            head_dim=16, vocab=128,
        )
    from triton_dist_tpu.models import (
        MoETransformerConfig, init_moe_params, quantize_moe_serving_params,
    )

    params = (
        init_moe_params(jax.random.PRNGKey(0), cfg)
        if isinstance(cfg, MoETransformerConfig)
        else init_params(jax.random.PRNGKey(0), cfg)
    )
    if isinstance(cfg, MoETransformerConfig) and (
        os.environ.get("TDT_SERVING_BENCH_QUANT") == "1"
    ):
        # int8 expert banks: the weight-bound decode MLP reads half the
        # bytes (quantize_moe_serving_params; run the same preset with
        # and without this env var for the uplift)
        params = quantize_moe_serving_params(params)
        name += "+w8"
    # EP presets (":ep" suffix) serve the expert-parallel deployment; the
    # hierarchical one (":ep-hier", ep_outer="dcn") needs the 2-axis mesh
    # — degenerate (1, 1) on a single chip, which still runs the full
    # two-phase dispatch program (the deployment the multi-slice serving
    # preset scales up; dryrun_multichip token-checks it at 2×4)
    ep_outer = getattr(cfg, "ep_outer", None)
    if ep_outer is not None:
        mesh = jax.sharding.Mesh(
            np.array(jax.devices()[:1]).reshape(1, 1), (ep_outer, cfg.axis)
        )
    else:
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("tp",))

    batcher = ContinuousBatcher(cfg, params, mesh, s_max=s_max)
    rng = np.random.default_rng(0)

    def keep_full():
        # steady state: every slot always busy (requests sized to outlast
        # the probe, resubmitted on completion)
        while len(batcher.queue) < batch:
            batcher.submit(Request(
                list(rng.integers(0, cfg.vocab, 8)),
                max_new_tokens=s_max - 16,
            ))

    keep_full()
    for _ in range(8):  # warmup: admission + first compiles
        batcher.step()
    t0 = time.perf_counter()
    for _ in range(steps):
        keep_full()
        batcher.step()
    dt = time.perf_counter() - t0
    tps = batch * steps / dt
    print(
        f"[serving_bench] {name} layers={n_layers} b={batch}: "
        f"{tps:.1f} tokens/s ({dt / steps * 1e3:.2f} ms/step, "
        f"host-synced continuous batching, {jax.devices()[0].platform})"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
