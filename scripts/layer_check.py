"""Real-shape single-layer check on a real chip: one decoder block of a
named preset (default LLaMA-3.1-8B) runs forward at its true hidden/ffn
shapes through the fused-kernel path; the output is checked for shape,
finiteness and non-degeneracy (numerical goldens live in the test suite —
this probe is COMPILE-AND-RUN evidence at real shapes, which toy test
dims can't give). The shapes are the ones the reference benchmarks
(its perf suite sweeps these same N/K, reference
test_ag_gemm.py:149-156).

    python scripts/layer_check.py [preset] [seq]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from triton_dist_tpu.models import (
    MoETransformerConfig, TPMoETransformer, TPTransformer, init_moe_params,
    init_params, moe_param_specs, param_specs, presets,
)


def main() -> int:
    name = sys.argv[1] if len(sys.argv) > 1 else "llama-3.1-8b"
    seq = int(sys.argv[2]) if len(sys.argv) > 2 else 2048
    interp = os.environ.get("TDT_LAYER_CHECK_INTERPRET") == "1"
    from triton_dist_tpu import config as tdt_config

    # loud: a fused op that cannot build fails the check, never its golden
    tdt_config.update(fallback_to_xla=False)
    if interp:
        jax.config.update("jax_platforms", "cpu")
        tdt_config.update(interpret=True)
        seq = min(seq, 64)
    elif jax.default_backend() != "tpu":
        print(f"ERROR: no TPU (backend={jax.default_backend()})",
              file=sys.stderr)
        return 1

    # small vocab: the embed/lm_head are not what this checks, and the
    # real 128k vocab would dominate HBM for a single-layer probe
    import dataclasses

    cfg = presets.preset(
        name, batch=1, seq=seq, n_layers=1,
        dtype=jnp.float32 if interp else jnp.bfloat16,
    )
    cfg = dataclasses.replace(cfg, vocab=512)
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    moe = isinstance(cfg, MoETransformerConfig)
    model = (TPMoETransformer if moe else TPTransformer)(cfg)
    params = (init_moe_params if moe else init_params)(jax.random.PRNGKey(0), cfg)
    specs = (moe_param_specs if moe else param_specs)(cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (cfg.batch * cfg.seq,), 0, cfg.vocab, jnp.int32
    )

    logits = jax.jit(
        jax.shard_map(
            lambda t, p: model(t, p),
            mesh=mesh,
            in_specs=(P("tp"), specs),
            out_specs=P(None, "tp"),
            check_vma=False,
        )
    )(tokens, params)
    jax.block_until_ready(logits)
    arr = np.asarray(logits, np.float32)
    assert arr.shape == (cfg.batch * cfg.seq, cfg.vocab), arr.shape
    assert np.isfinite(arr).all(), "non-finite logits"
    # golden: greedy next-token distribution should be non-degenerate
    # (catches all-zero / collapsed outputs that finite checks miss)
    assert len(np.unique(arr.argmax(-1))) > 1, "degenerate logits"
    print(
        f"[layer_check] {name}: 1 layer fwd @ hidden={cfg.hidden} "
        f"ffn={cfg.ffn} seq={cfg.seq} OK on {jax.devices()[0].platform}"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
