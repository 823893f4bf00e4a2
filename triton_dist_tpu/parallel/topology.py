"""Topology discovery for TPU slices.

TPU-native analogue of the reference's NVLink/NUMA probing
(``python/triton_dist/utils.py:504-786``: ``get_has_fullmesh_nvlink``,
``get_numa_world_size``, ``check_p2p_native_atomic_supported``,
``get_intranode_max_speed``). On TPU the questions become: what are the
physical torus coordinates of each device (``device.coords``), is the mesh
axis a wrap-around ring, and what per-link ICI bandwidth to assume for
method auto-selection and perf models.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax


# Per-direction ICI link bandwidth, GB/s (one link). Conservative public
# numbers; used only for auto-selection heuristics and SOL perf models
# (≙ reference get_intranode_max_speed, utils.py:742).
ICI_GBPS = {
    "v4": 50.0,
    "v5e": 45.0,
    "v5p": 100.0,
    "v6e": 90.0,
    "cpu": 1.0,  # interpreter/testing
}

# Dense bf16 peak TFLOPs per chip (≙ gemm_perf_model.py tensor-core tables).
PEAK_BF16_TFLOPS = {
    "v4": 275.0,
    "v5e": 197.0,
    "v5p": 459.0,
    "v6e": 918.0,
    "cpu": 0.1,
}

HBM_GBPS = {
    "v4": 1200.0,
    "v5e": 819.0,
    "v5p": 2765.0,
    "v6e": 1640.0,
    "cpu": 50.0,
}

# Per-host DCN (data-center network) bandwidth, GB/s — the inter-slice
# fabric of Multislice TPU (≙ the reference's inter-node IB plane,
# utils.py:742 internode speeds). Conservative public 200 Gbps NIC figure;
# used only by perf models and method auto-selection, never correctness.
DCN_GBPS = 25.0

# On-core VMEM per generation, MiB (public figures; like HBM_GBPS this
# steers heuristics — kernel auto-modes size their scratch against it —
# never correctness). An unknown generation raises (`_known`).
VMEM_MIB = {
    "v4": 128,
    "v5e": 128,
    "v5p": 128,
    "v6e": 128,
    "cpu": 128,
}


def _known(table: dict, gen: str, what: str):
    if gen not in table:
        raise ValueError(
            f"no {what} on record for TPU generation {gen!r} "
            f"(have {sorted(table)}); add it to parallel/topology.py "
            f"rather than run on a guess"
        )
    return table[gen]


def vmem_bytes(gen: str | None = None) -> int:
    return _known(VMEM_MIB, gen or tpu_generation(), "VMEM size") * 2**20


def tpu_generation() -> str:
    """TPU generation string ('v5e', 'v5p', ...), or 'cpu' off-TPU. A
    device kind this table does not know raises: the VMEM size and link
    rate of some other generation are not a safe guess."""
    devs = jax.devices()
    if not devs or devs[0].platform != "tpu":
        return "cpu"
    kind = getattr(devs[0], "device_kind", "").lower()
    for gen in ("v6e", "v5p", "v5e", "v4"):
        if gen in kind.replace(" ", "").replace("lite", "e"):
            return gen
    if "v5" in kind:
        return "v5e" if "lite" in kind else "v5p"
    raise ValueError(
        f"unknown TPU device_kind {devs[0].device_kind!r}: add its "
        f"generation to parallel/topology.py"
    )


def has_wraparound(
    axis_size: int, devices: Sequence[jax.Device] | None = None
) -> bool:
    """Whether a mesh axis of this size forms a wrap-around torus ring
    (≙ reference ``get_has_fullmesh_nvlink``, utils.py:762 — the question
    that steers collective-method auto-selection).

    Decision procedure:

    1. Interpreter/CPU: True (the simulated ring is whatever we say it is).
    2. ``axis_size`` ≤ 2: trivially True (one link serves both directions).
    3. With `devices` (the devices along the axis): read their physical
       ``coords``. A ring exists only if exactly one torus coordinate
       varies, contiguously. Given that, wrap links exist per generation:
       v4/v5p build 3-D tori with OCS wrap when a slice dimension is a
       multiple of 4; v5e/v6e are 2-D meshes whose only wrap is a full
       16-chip pod edge.
    4. Without `devices` (or coords unavailable): same per-generation rule
       applied to ``axis_size`` alone.
    """
    gen = tpu_generation()
    if gen == "cpu":
        return True
    if axis_size <= 2:
        return True
    span = axis_size
    if devices is not None:
        coords = device_coords(devices)
        if coords is not None:
            ndim = len(coords[0])
            varying = [
                i for i in range(ndim) if len({c[i] for c in coords}) > 1
            ]
            if len(varying) != 1:
                return False  # axis snakes through >1 torus dim: no ring wrap
            vals = sorted({c[varying[0]] for c in coords})
            if vals != list(range(vals[0], vals[0] + len(vals))):
                return False  # non-contiguous placement
            span = len(vals)
    if gen in ("v4", "v5p"):
        return span % 4 == 0
    return span >= 16  # v5e/v6e: wrap only on a full 2-D pod edge


@dataclasses.dataclass(frozen=True)
class LinkSpec:
    gbps: float
    generation: str


def ici_link(gen: str | None = None) -> LinkSpec:
    g = gen or tpu_generation()
    return LinkSpec(gbps=_known(ICI_GBPS, g, "ICI link rate"), generation=g)


def axis_devices(mesh, axis: str):
    """The devices along one mesh axis (other axes fixed at index 0) — what
    :func:`has_wraparound` wants for physical ring detection."""
    ax = tuple(mesh.axis_names).index(axis)
    idx: list = [0] * mesh.devices.ndim
    idx[ax] = slice(None)
    return list(mesh.devices[tuple(idx)])


def device_coords(devices: Sequence[jax.Device] | None = None):
    """Physical coords of each device, or None on non-TPU backends."""
    devices = list(devices if devices is not None else jax.devices())
    coords = []
    for d in devices:
        c = getattr(d, "coords", None)
        if c is None:
            return None
        coords.append(tuple(c))
    return coords


def device_slice_ids(devices: Sequence[jax.Device] | None = None):
    """Multislice slice index per device, or None when the backend does
    not report one (single-slice TPU, CPU, interpreter). Devices with
    different slice ids have NO ICI path between them — only DCN
    (≙ the reference's node boundary: ranks on different hosts reach each
    other over IB, not NVLink)."""
    devices = list(devices if devices is not None else jax.devices())
    ids = []
    for d in devices:
        s = getattr(d, "slice_index", None)
        if s is None:
            return None
        ids.append(int(s))
    return ids


def axis_crosses_slices(mesh, axis: str) -> bool:
    """Whether stepping along `axis` ever crosses a slice boundary — i.e.
    whether this axis's collectives ride DCN. False when slice ids are
    unavailable (single-slice and test backends).

    EVERY column along the axis is checked (all positions of the other
    axes, not just index 0): a user-ordered mesh can be slice-uniform in
    one column and slice-crossing in another, and a miss here would send
    remote DMA across a boundary with no ICI path."""
    import numpy as _np

    ids = device_slice_ids(list(mesh.devices.reshape(-1)))
    if ids is None:
        return False
    ax = tuple(mesh.axis_names).index(axis)
    grid = _np.array(ids).reshape(mesh.devices.shape)
    cols = _np.moveaxis(grid, ax, 0).reshape(grid.shape[ax], -1)
    return bool((cols != cols[0:1]).any())


# Auto-DETECTED slice-crossing axis names, refreshed per make_mesh call:
# a new mesh overwrites the verdict for ITS axis names (so a later
# single-slice mesh reusing a name is not poisoned by an earlier
# Multislice mesh), while names it doesn't use keep their last verdict.
# User DECLARATIONS live separately in config.dcn_axes and are never
# touched here.
_DETECTED_DCN: set = set()


def register_mesh_dcn(mesh) -> tuple[str, ...]:
    """Record which of `mesh`'s axes cross slice boundaries (called by
    ``parallel.mesh.make_mesh``). Returns the detected tuple."""
    detected = detect_dcn_axes(mesh)
    for ax in mesh.axis_names:
        _DETECTED_DCN.discard(ax)
    _DETECTED_DCN.update(detected)
    return detected


def detect_dcn_axes(mesh) -> tuple[str, ...]:
    """The mesh axes whose hops cross slice boundaries, in mesh order."""
    return tuple(
        ax for ax in mesh.axis_names if axis_crosses_slices(mesh, ax)
    )


# ---------------------------------------------------------------------------
# Elastic shrink (resilience/elastic.py): a quarantined PE is excised from
# the world and the comm topology is re-derived over the survivors.
# ---------------------------------------------------------------------------

def surviving_ring(axis_size: int, quarantined) -> tuple[int, ...]:
    """Ring order of the surviving flattened positions after dropping
    ``quarantined`` from an axis of ``axis_size`` PEs. Survivors keep their
    relative order, so the shrunk ring is the old ring with the sick hops
    spliced out — each survivor's new neighbor is its nearest surviving
    ex-neighbor. Raises if nothing survives (an all-quarantined world is an
    operator problem, not a topology)."""
    dropped = {int(q) for q in quarantined}
    bad = [q for q in dropped if not 0 <= q < axis_size]
    if bad:
        raise ValueError(
            f"quarantined positions {sorted(bad)} outside axis of size "
            f"{axis_size}"
        )
    ring = tuple(i for i in range(axis_size) if i not in dropped)
    if not ring:
        raise ValueError(
            f"all {axis_size} PEs quarantined — no surviving topology"
        )
    return ring


def remap_world(axis_size: int, quarantined) -> dict[int, int]:
    """Old→new flattened index for the survivors of a shrink — the rank
    remapping collectives and shardings are re-derived under (quarantined
    positions are absent from the map)."""
    return {old: new for new, old in
            enumerate(surviving_ring(axis_size, quarantined))}


def torus_factor(n: int) -> tuple[int, int]:
    """Most-square 2-D torus factorization ``(outer, inner)`` of an axis of
    ``n`` PEs: ``inner`` is the largest divisor of ``n`` at most ``√n``
    (``inner <= outer``, ``outer * inner == n``). This is the standing
    question 2-D-aware schedules ask of a flattened mesh axis — e.g. the
    synthesized ``torus2d`` span policy (``ops.common.span_torus2d_schedule``)
    sizes its chunk count to the inner ring so each forwarded span crosses
    one inner-axis hop. Worlds with no square-ish factorization (primes,
    n <= 2) return ``(n, 1)`` — a line, no inner ring."""
    n = int(n)
    if n < 1:
        raise ValueError(f"torus_factor: world must be >= 1, got {n}")
    inner = 1
    d = 2
    while d * d <= n:
        if n % d == 0:
            inner = d
        d += 1
    return n // inner, inner


def is_dcn_axis_name(name) -> bool:
    """Whether collectives on this axis name must ride DCN: declared via
    ``config.dcn_axes`` (user) or auto-detected for the latest mesh using
    the name (``register_mesh_dcn``)."""
    from triton_dist_tpu import config as tdt_config

    return name in tdt_config.get_config().dcn_axes or name in _DETECTED_DCN
