"""ctypes bindings for the native C++ components in ``csrc/``
(≙ reference pybind registry ``csrc/lib/registry.{h,cc}`` +
``op_pybind.cc`` exposing ``triton._C.libtriton_distributed.distributed``;
ctypes instead of pybind per the build-environment constraints).

The library is built on demand with the in-tree Makefile (g++ is a baked-in
tool); every binding has a numpy fallback so the package works without a
compiler too.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

def _find_csrc_dir() -> str:
    """Source checkout keeps csrc/ at the repo root; installed wheels carry
    a copy inside the package (setup.py BuildWithNative)."""
    pkg = os.path.dirname(os.path.abspath(__file__))
    for cand in (os.path.join(os.path.dirname(pkg), "csrc"),
                 os.path.join(pkg, "csrc")):
        if os.path.isdir(cand):
            return cand
    return os.path.join(os.path.dirname(pkg), "csrc")  # legacy default


_CSRC_DIR = _find_csrc_dir()
_LIB_PATH = os.path.join(_CSRC_DIR, "libtdt_native.so")
_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_lib_failed = False


def _load() -> ctypes.CDLL | None:
    """Load (building if needed) the native library; None if unavailable."""
    global _lib, _lib_failed
    if _lib is not None or _lib_failed:
        return _lib
    with _lock:
        if _lib is not None or _lib_failed:
            return _lib
        try:
            src_mtime = max(
                os.path.getmtime(os.path.join(_CSRC_DIR, f))
                for f in os.listdir(_CSRC_DIR)
                if f.endswith((".cc", ".h"))
            )
            if (
                not os.path.exists(_LIB_PATH)
                or os.path.getmtime(_LIB_PATH) < src_mtime
            ):
                # build to a per-process temp name + atomic rename so
                # concurrent processes never dlopen a half-written .so
                tmp = f"libtdt_native.so.tmp.{os.getpid()}"
                subprocess.run(
                    ["make", "-C", _CSRC_DIR, "-s", "-B", f"LIB={tmp}"],
                    check=True,
                    capture_output=True,
                    timeout=120,
                )
                os.replace(os.path.join(_CSRC_DIR, tmp), _LIB_PATH)
            lib = ctypes.CDLL(_LIB_PATH)
            lib.tdt_abi_version.restype = ctypes.c_int
            if lib.tdt_abi_version() != 1:
                raise RuntimeError("tdt_native ABI mismatch")
            lib.tdt_moe_align_block_size.restype = ctypes.c_int
            lib.tdt_moe_align_block_size.argtypes = [
                ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
                ctypes.c_int32, ctypes.c_int32, ctypes.c_int64,
                ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
                ctypes.POINTER(ctypes.c_int32),
            ]
            _lib = lib
        except Exception:
            _lib_failed = True
    return _lib


def native_available() -> bool:
    return _load() is not None


def moe_align_block_size_host(
    topk_ids: np.ndarray, n_experts: int, block_m: int
) -> tuple[np.ndarray, np.ndarray, int]:
    """Host-side block alignment over numpy arrays (native C++ when
    available, numpy otherwise). Same contract as the device-side
    ``ops.moe_utils.moe_align_block_size``."""
    topk_ids = np.ascontiguousarray(topk_ids, np.int32)
    t = topk_ids.shape[0]
    t_pad = -(-(t + min(n_experts, t) * (block_m - 1)) // block_m) * block_m
    lib = _load()
    if lib is not None:
        sorted_ids = np.empty(t_pad, np.int32)
        expert_ids = np.empty(t_pad // block_m, np.int32)
        n_post = np.empty(1, np.int32)
        rc = lib.tdt_moe_align_block_size(
            topk_ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            t, n_experts, block_m, t_pad,
            sorted_ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            expert_ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            n_post.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        )
        if rc != 0:
            raise ValueError(f"tdt_moe_align_block_size failed: rc={rc}")
        return sorted_ids, expert_ids, int(n_post[0])
    # fallback: delegate to the (single) device-side implementation so the
    # two paths cannot drift; validate like the native library (rc=-2)
    if t and (topk_ids.min() < 0 or topk_ids.max() >= n_experts):
        raise ValueError(
            f"tdt_moe_align_block_size failed: rc=-2 (expert id out of "
            f"range 0..{n_experts - 1})"
        )
    from triton_dist_tpu.ops.moe_utils import moe_align_block_size

    al = moe_align_block_size(jnp_asarray(topk_ids), n_experts, block_m)
    return (
        np.asarray(al.sorted_token_ids),
        np.asarray(al.expert_ids),
        int(al.num_tokens_post_pad),
    )


def jnp_asarray(x: np.ndarray):
    import jax.numpy as jnp

    return jnp.asarray(x)
