#!/usr/bin/env python3
"""Rehearsal without the chip: compile a cell's decode step and prefill
buckets at its real widths for a DESCRIBED ``v5e:2x2`` (the chip's own
compiler, no chip attached) and print each program's ``memory_analysis``.
Run with ``JAX_PLATFORMS=cpu``. A compile that passes is not a run, and
nothing it prints is a device metric.

    JAX_PLATFORMS=cpu python3 perfbench/describe.py --workload <cell>
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from harness import cells, traffic  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args()
    cell = cells.Cell(cells.benchmark(), args.workload)
    spec = traffic.load(cell.traffic_path)

    from triton_dist_tpu import config as tdt_config
    from triton_dist_tpu.models import decode as dec
    from triton_dist_tpu.models.tp_transformer import param_specs
    from triton_dist_tpu.ops.common import jit_shard_map

    jax.config.update("jax_enable_compilation_cache", False)
    tdt_config.update(fallback_to_xla=False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    s, eng = cell.config["sizes"], cell.config["engine"]
    adapter = cells.load_module("programs", cell.config["program"])
    ref = cells.load_module("references", cell.config["reference"])
    cfg = adapter.transformer_config(cell.config, interpret=False)
    n = cell.chips
    mesh = Mesh(np.array(topo.devices[:n]), (cfg.axis,))
    kv = dec.PagedKVCacheSpec(eng["s_max"], eng["page"], static_table=True)

    def abstract(tree, specs):
        return jax.tree.map(
            lambda x, p: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, p)),
            tree, specs)

    layer = jax.eval_shape(
        lambda k: adapter.pack_layer(ref.layer_weights(k, 0, s), s),
        jax.random.PRNGKey(0))
    outer = jax.eval_shape(lambda k: ref.outer_weights(k, s), jax.random.PRNGKey(0))
    p_specs = param_specs(cfg)
    params = abstract(dict(outer, layers=[layer] * s["n_layers"]), p_specs)
    cache = abstract(jax.eval_shape(lambda: kv.init(cfg, n, 1)), kv.specs(cfg))
    b = cfg.batch
    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=NamedSharding(mesh, P(*[None] * len(shape))))
    weights = sum(np.prod(x.shape) * x.dtype.itemsize
                  for x in jax.tree.leaves(params))
    pool = sum(np.prod(cache[k].shape) * cache[k].dtype.itemsize for k in "kv")
    print(f"{cell.name}: weights {weights / 1e9:.2f} GB and KV pool "
          f"{pool / 1e9:.2f} GB over {n} chip(s)", flush=True)

    def report(name, prog, *a):
        t0 = time.perf_counter()
        compiled = prog.jitted.lower(*a).compile()
        m = compiled.memory_analysis()
        text = compiled.as_text()
        print(f"{name}: compiled in {time.perf_counter() - t0:.1f} s; per "
              f"device: arguments {m.argument_size_in_bytes / 1e9:.2f} GB, "
              f"outputs {m.output_size_in_bytes / 1e9:.2f} GB, aliased "
              f"{m.alias_size_in_bytes / 1e9:.2f} GB, temps "
              f"{m.temp_size_in_bytes / 1e9:.2f} GB, peak about "
              f"{(m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes) / 1e9:.2f} GB; "
              f"{text.count('tpu_custom_call')} custom calls, "
              f"{text.count('all-gather-start') + text.count(' all-gather(')} all-gathers",
              flush=True)

    step = jit_shard_map(
        functools.partial(dec.decode_step, cfg, spec=kv, fd_config=None,
                          interpret=False),
        mesh, (p_specs, kv.specs(cfg), P(None), P(None)),
        (P(None, None), kv.specs(cfg)),
        key=("describe_step", cfg, kv), donate_argnums=(1,))
    report("decode step", step, params, cache, i32(b), i32(b))

    reqs = traffic.generate(spec, s["vocab"], 0, args.seconds)
    buckets = sorted({1 << max(0, (len(r.prompt) - 1).bit_length()) for r in reqs})
    for bucket in buckets:
        pcfg = dataclasses.replace(cfg, seq=bucket)

        def fn(params, cache, prompt, mask, pick, pcfg=pcfg, bucket=bucket):
            return dec.prefill_cache(
                pcfg, params, cache, dec._prompt_shard(prompt, b, bucket, cfg),
                kv, eng["s_max"], slot_mask=mask, pick=pick)

        prog = jit_shard_map(
            fn, mesh,
            (p_specs, kv.specs(cfg), P(None, None), P(None), P(None)),
            (kv.specs(cfg), P(None, None)),
            key=("describe_prefill", cfg, kv, bucket), donate_argnums=(1,))
        mask = jax.ShapeDtypeStruct(
            (b,), jnp.bool_, sharding=NamedSharding(mesh, P(None)))
        report(f"prefill bucket {bucket}", prog, params, cache,
               i32(b, bucket), mask, i32(b))
    return 0


if __name__ == "__main__":
    sys.exit(main())
