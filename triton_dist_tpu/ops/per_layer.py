"""What a kernel that a model calls ONCE A LAYER needs from its host side,
so that the per-layer calls of one program cost set-up and the step no
more than one call does.

- :func:`traced_once`: the layers' calls differ in their operands only (the
  layer is a prefetched scalar, not a constant of the index maps), so the
  host function is jitted and a program's 26 calls trace the kernel's body
  and lower it to Mosaic ONCE. Traced anew a call, the two kernels of a
  state-space layer were a quarter of a step program's lowering.
- :func:`in_hbm`: a small operand (a ``[d]`` vector, the taps) is pinned to
  HBM and fetched by the kernel's own pipeline. Left to the compiler it is
  prefetched into fast memory by a copy of its own, queued behind the
  weight stream, and the kernel waits for that copy.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu
from jax.extend.source_info_util import current_name_stack

from triton_dist_tpu import config as tdt_config


def layer_index(li) -> jax.Array:
    """The layer as the ``[1]`` int32 vector a kernel prefetches."""
    return jnp.full((1,), li, jnp.int32)


def traced_once(fn):
    """``fn(*arrays, interpret=)`` behind ``jax.jit``: calls with the same
    shapes share one trace and one lowering. ``interpret`` is resolved
    before the boundary and the caller's named scopes are read there (both
    are part of the key). An ARMED run (watchdog, fault plan) traces every
    call: ``dist_pallas_call`` keeps its books while it traces."""
    def run(*args, interpret, scope):
        # a jitted function's ops start a name stack of their own: the
        # caller's scopes (obs/scopes.py: ``tdt.ssm/conv``) are re-opened
        with jax.named_scope(scope) if scope else contextlib.nullcontext():
            return fn(*args, interpret=interpret)

    run.__name__ = run.__qualname__ = fn.__name__
    jitted = jax.jit(run, static_argnames=("interpret", "scope"))

    @functools.wraps(fn)
    def call(*args, interpret=None):
        cfg = tdt_config.get_config()
        if interpret is None:
            interpret = tdt_config.interpret_params()
        if int(cfg.timeout_iters) > 0 or cfg.fault_plan is not None:
            return fn(*args, interpret=interpret)
        return jitted(*args, interpret=interpret,
                      scope=str(current_name_stack()))

    return call


def in_hbm(x, interpret):
    """``x`` as an operand its kernel fetches from HBM itself (traced
    calls only; the interpreter knows no memory spaces and gets ``x`` as
    it is)."""
    if interpret:
        return x
    return pltpu.with_memory_space_constraint(x, pltpu.HBM)
