#!/usr/bin/env python3
"""The planted faults of ``brumby-14b-base.doc-reason-b16``: each one
thing a power-retention serving path can get wrong, patched into the
program BEFORE its passes are traced, so that the comparison that decides
``correct`` can be shown to fail it (PERF.md section 4 keeps the readings;
``tests/test_retention.py`` plants the same faults at toy widths).

    python3 perfbench/tools/retention_faults.py --fault bf16_state \\
        --workload brumby-14b-base.doc-reason-b16 --seed 7 --seconds 45

runs the cell as ``run.py`` does with the fault in place and prints the
same result line. The faults:

``bf16_state``          every state a kernel writes (``S``, ``Z``; the
                        step's and the admission's carry) rounded to
                        bfloat16
``gate_dropped``        ``g = 1``: nothing is forgotten
``normaliser_dropped``  ``y = sum_j a_ij v_j``, not divided by ``sum_j a_ij``
``stale_state_kept``    an admission adds its state to what the slot held
                        instead of overwriting it
``step_twice``          every step's update applied twice to the state it
                        writes (what a repeated step does to a state that is
                        not keyed by its position's parity)
"""

from __future__ import annotations

import argparse
import contextlib
import inspect
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(HERE), HERE]

FAULTS = ("bf16_state", "gate_dropped", "normaliser_dropped",
          "stale_state_kept", "step_twice")
def _then(kernel, after):
    """``kernel`` followed, every grid step, by ``after(refs by name)``:
    the names are the kernel's own parameters less their ``_ref``."""
    names = [p[:-4] for p in inspect.signature(kernel).parameters
             if p.endswith("_ref")]

    def both(*refs, **kw):
        kernel(*refs, **kw)
        after(dict(zip(names, refs)), **kw)

    return both


def _patches(fault: str) -> list:
    """``(object, attribute, value)`` of one fault."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from triton_dist_tpu.models import decode, retention as model
    from triton_dist_tpu.ops import retention as rt

    bf16 = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)

    def each(ref, fn):
        ref[...] = fn(ref[...])

    if fault == "bf16_state":
        def update(r, **kw):
            each(r["s_out"], bf16)
            each(r["z_out"], bf16)

        def prefill(r, **kw):
            each(r["s"], bf16)
            each(r["z"], bf16)

        return [(rt, "_update_kernel", _then(rt._update_kernel, update)),
                (rt, "_prefill_kernel", _then(rt._prefill_kernel, prefill))]
    if fault == "gate_dropped":
        return [(model, "_log_gate", lambda u, p: jnp.zeros(
            (u.shape[0], p["w_g"].shape[1]), jnp.float32))]
    if fault == "normaliser_dropped":
        def update(r, eps, **kw):
            @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
            def _():
                for h in range(r["den"].shape[0]):
                    r["y"][0, 0, h:h + 1, :] = (
                        r["y"][0, 0, h:h + 1, :] * (r["den"][h] + eps))

        def prefill(r, **kw):
            for h in range(r["den"].shape[0]):
                r["yt"][0, h] = (r["yt"][0, h].astype(jnp.float32)
                                 * r["den"][h]).astype(r["yt"].dtype)

        return [(rt, "_update_kernel", _then(rt._update_kernel, update)),
                (rt, "_prefill_kernel", _then(rt._prefill_kernel, prefill))]
    if fault == "stale_state_kept":
        write = decode.RetentionStateCacheSpec.write_state

        def kept(self, cache, li, slots, lens, s, z):
            at = (li, (lens - 1) % 2, slots)
            return write(self, cache, li, slots, lens, s + cache["s"][at],
                         z + cache["z"][at])

        return [(decode.RetentionStateCacheSpec, "write_state", kept)]
    if fault == "step_twice":
        def update(r, **kw):
            gate = r["kvg"][0, 0, 2:3, :]
            first = r["pos"][pl.program_id(0)] == 0

            def again(ref_in, ref_out):
                old, new = ref_in[0, 0, 0, 0], ref_out[0, 0, 0, 0]
                old = jnp.where(jnp.broadcast_to(first, old.shape), 0.0, old)
                ref_out[0, 0, 0, 0] = gate * new + (new - gate * old)

            again(r["s_in"], r["s_out"])

            @pl.when(pl.program_id(2) == 0)
            def _():
                again(r["z_in"], r["z_out"])

        return [(rt, "_update_kernel", _then(rt._update_kernel, update))]
    raise ValueError(f"{fault!r} is not one of {FAULTS}")


@contextlib.contextmanager
def planted(fault: str):
    """The program with ``fault`` in it, for what is traced inside the
    block: every cached trace goes before and after (the kernels' host
    functions are jitted once a shape)."""
    import jax

    patches = _patches(fault)
    saved = [(obj, attr, getattr(obj, attr)) for obj, attr, _ in patches]
    jax.clear_caches()
    for obj, attr, value in patches:
        setattr(obj, attr, value)
    try:
        yield
    finally:
        for obj, attr, value in saved:
            setattr(obj, attr, value)
        jax.clear_caches()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--fault", required=True, choices=FAULTS)
    args, rest = ap.parse_known_args(argv)
    import run

    with planted(args.fault):
        return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
