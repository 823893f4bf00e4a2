#!/usr/bin/env python3
"""The planted faults of ``granite-4.0-h-small-ep4.doc-reason``: each one
thing a serving path over a Mamba-2 state can get wrong, put into the
program so that the comparison that decides ``correct`` can be shown to
fail it (PERF.md section 4 keeps the readings;
``perfbench/tests/test_ssd_moe.py`` plants the same faults at toy widths).

    python3 perfbench/tools/ssd_faults.py --fault carry_dropped \\
        --workload granite-4.0-h-small-ep4.doc-reason --seed 7 --seconds 45

runs the cell as ``run.py`` does with the fault in place and prints the
same result line. The faults:

``stale_state_kept``  an admission leaves the slot's old recurrent state in
                      place (it writes the convolution's ring and the pages
                      and not the state): the warm-up's admissions and
                      every earlier request leave theirs behind
``advanced_twice``    in the round after an admission the admitted slot's
                      state is advanced twice by its first token (what a
                      step sent in vain and run again does to a state that
                      is not keyed by its position's parity): after the
                      admission the step runs once more and the row it
                      wrote is copied over the row the next step reads
``carry_dropped``     the carried state is dropped at ONE chunk edge of
                      every admission's dual form, the LAST one a prompt
                      has (before its last live chunk): the rows past it,
                      and the state the slot is left with, see nothing of
                      the rows before it. (An edge thousands of rows back
                      is forgotten by the time the answer starts, by the
                      model itself: ``exp(-7000 dt A)``.)
"""

from __future__ import annotations

import argparse
import contextlib
import inspect
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(HERE), HERE]

FAULTS = ("stale_state_kept", "advanced_twice", "carry_dropped")
def _then(kernel, after):
    """``kernel`` followed, every grid step, by ``after(refs by name)``:
    the names are the kernel's own parameters less their ``_ref``."""
    names = [p[:-4] for p in inspect.signature(kernel).parameters
             if p.endswith("_ref")]

    def both(*refs, **kw):
        kernel(*refs, **kw)
        after(dict(zip(names, refs)))

    return both


def _patches(fault: str) -> list:
    """``(object, attribute, value)`` of one fault in the program's code."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from triton_dist_tpu.models import decode
    from triton_dist_tpu.ops import ssd

    if fault == "stale_state_kept":
        write = decode.StatePagedKVCacheSpec.write_state

        def kept(self, cache, ki, slots, lens, u, h, first=None):
            old = cache["ssm"]
            return dict(write(self, cache, ki, slots, lens, u, h, first),
                        ssm=old)

        return [(decode.StatePagedKVCacheSpec, "write_state", kept)]
    if fault == "carry_dropped":
        def drop(r):
            @pl.when(pl.program_id(1) == r["live"][0] - 2)
            def _():
                r["h"][:] = jnp.zeros(r["h"].shape, jnp.float32)

        return [(ssd, "_scan_kernel", _then(ssd._scan_kernel, drop))]
    if fault == "advanced_twice":
        return []
    raise ValueError(f"{fault!r} is not one of {FAULTS}")


def advance_twice(system) -> None:
    """``run.main(tamper=)`` of ``advanced_twice``: wraps the batcher's
    first-token hook, which every admission by prefill ends with."""
    import jax
    import jax.numpy as jnp

    batcher = system.engine._batcher
    first_token = batcher._first_token
    # the row of position p (p % 2) over the row the step at p + 1 reads
    # ((p + 1 - 1) % 2 is the same row: so over the row the step at p reads,
    # which the SAME step, run again by the round, advances a second time)
    copy = jax.jit(
        lambda ssm, slot, p: ssm.at[:, (p + 1) % 2, slot].set(
            ssm[:, p % 2, slot]), donate_argnums=0)

    def then_again(i, req, last_i):
        first_token(i, req, last_i)
        p = int(batcher.pos[i])
        if batcher.slot_req[i] is None or p != len(req.prompt):
            return                          # finished at once
        _, cache = batcher._step(batcher.params, batcher.cache,
                                 jnp.asarray(batcher.tok),
                                 jnp.asarray(batcher.pos))
        batcher.cache = dict(cache, ssm=copy(cache["ssm"], i, p))

    batcher._first_token = then_again


@contextlib.contextmanager
def planted(fault: str):
    """The program with ``fault`` in its code, for what is traced inside
    the block: every cached trace goes before and after (the kernels' host
    functions are jitted once a shape)."""
    import jax

    from triton_dist_tpu.ops import ssd

    patches = _patches(fault)
    saved = [(obj, attr, getattr(obj, attr)) for obj, attr, _ in patches]
    jax.clear_caches()
    ssd._chunk_scan_of.cache_clear()
    for obj, attr, value in patches:
        setattr(obj, attr, value)
    try:
        yield
    finally:
        for obj, attr, value in saved:
            setattr(obj, attr, value)
        jax.clear_caches()
        ssd._chunk_scan_of.cache_clear()


def tamper_of(fault: str):
    """What ``run.main(tamper=)`` takes for ``fault`` (None: the fault is
    in the code alone)."""
    return advance_twice if fault == "advanced_twice" else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--fault", required=True, choices=FAULTS)
    args, rest = ap.parse_known_args(argv)
    import run

    with planted(args.fault):
        return run.main(rest, tamper=tamper_of(args.fault))


if __name__ == "__main__":
    sys.exit(main())
