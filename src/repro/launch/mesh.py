"""Production mesh construction + SFC device placement (DESIGN.md §15).

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Beyond-paper: ``device_order`` embeds the logical
(data, model) mesh onto the physical 2-D ICI torus along a space-filling
curve -- ``"hilbert"`` or ``"morton"`` -- so ring collectives on either
logical axis step between physically nearby chips: the paper's locality
idea applied to the *interconnect* (DESIGN.md §2, §15).  On this CPU
container the devices are placeholders, so the effect is structural; on
real hardware the permutation is what ``device_order`` would feed to
``mesh_utils``.

The honest structural claim (property-tested in
``tests/test_comm_placement.py``): a curve embedding wins when the
logical mesh axes do NOT coincide with the physical torus dims -- e.g. a
(32, 8) or (64, 4) logical mesh on a 16x16 torus, where row-major makes
every data-axis ring step jump half a torus row.  When the logical shape
equals the torus shape, row-major IS the identity embedding and is
already hop-optimal; :func:`link_distance` exposes the per-axis mean hop
counts so callers (and the tuner's :class:`repro.tune.cost.CommSpec`
term) can score the trade instead of assuming it.
"""
from __future__ import annotations

import weakref

import numpy as np

import jax

__all__ = ["DEVICE_ORDERS", "default_torus", "device_permutation",
           "link_distance", "make_production_mesh", "make_smoke_mesh",
           "mesh_chips", "mesh_device_order"]

# every supported device_order; anything else is a ValueError (a silent
# row-major fallback returned placements the caller never asked for)
DEVICE_ORDERS = ("rowmajor", "hilbert", "morton")

# which curve a mesh was built under, so link_distance(mesh) scores the
# embedding that actually ran without callers re-threading the flag.
# Weak: meshes die, the record follows.
_MESH_DEVICE_ORDER: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _record_device_order(mesh, order: str):
    try:
        _MESH_DEVICE_ORDER[mesh] = order
    except TypeError:  # non-weakref-able mesh stand-ins (tests)
        pass
    return mesh


def mesh_device_order(mesh) -> str:
    """The ``device_order`` a mesh was built under ("rowmajor" for
    meshes built elsewhere)."""
    return _MESH_DEVICE_ORDER.get(mesh, "rowmajor")


def default_torus(n: int) -> tuple[int, int]:
    """Assumed physical 2-D ICI torus for an ``n``-chip pod: the
    near-square power-of-two factorisation (256 -> 16x16, 8 -> 2x4)."""
    if n < 1 or n & (n - 1):
        raise ValueError(
            f"physical torus model needs a power-of-two chip count, "
            f"got {n}")
    rows = 1 << ((n.bit_length() - 1) // 2)
    return rows, n // rows


def device_permutation(order: str, rows: int, cols: int, devices) -> list:
    """Permute ``devices`` -- physically row-major over a (rows x cols)
    torus -- so that walking the flattened logical mesh follows the
    named curve over the physical torus.

    The shared helper behind every ``device_order``: the visit order
    comes from :func:`repro.core.schedule.grid_schedule` (the same
    memoised tables the GEMM kernels traverse) and is bijection-checked
    here -- a curve that skipped or repeated a chip would silently
    assign two logical ranks to one device, which jax would only report
    as a confusing duplicate-device error much later.
    """
    from repro.core.schedule import grid_schedule

    if order not in DEVICE_ORDERS:
        raise ValueError(
            f"unknown device_order {order!r}; supported orders: "
            f"{', '.join(DEVICE_ORDERS)}")
    devices = list(devices)
    if len(devices) != rows * cols:
        raise ValueError(
            f"{len(devices)} devices cannot tile a {rows}x{cols} torus")
    if order == "rowmajor":
        return devices
    visits = np.asarray(grid_schedule(order, rows, cols))
    in_bounds = ((visits[:, 0] >= 0) & (visits[:, 0] < rows)
                 & (visits[:, 1] >= 0) & (visits[:, 1] < cols))
    counts = np.bincount(
        visits[in_bounds, 0] * cols + visits[in_bounds, 1],
        minlength=rows * cols)
    if not in_bounds.all() or (counts != 1).any():
        raise ValueError(
            f"schedule {order!r} is not a bijection over "
            f"{rows}x{cols}: {int((~in_bounds).sum())} out of bounds, "
            f"{int((counts != 1).sum())} tiles not visited exactly once")
    grid = np.asarray(devices, dtype=object).reshape(rows, cols)
    return [grid[i, j] for (i, j) in visits]


def _torus_hops(a: np.ndarray, b: np.ndarray,
                torus: tuple[int, int]) -> np.ndarray:
    """Per-pair ICI hop count (torus Manhattan distance with wraparound)
    between physical coordinates ``a`` and ``b``, both (N, 2)."""
    rows, cols = torus
    dr = np.abs(a[:, 0] - b[:, 0])
    dc = np.abs(a[:, 1] - b[:, 1])
    return np.minimum(dr, rows - dr) + np.minimum(dc, cols - dc)


def link_distance(mesh, *, device_order: str | None = None,
                  torus: tuple[int, int] | None = None,
                  wrap: bool = True) -> dict[str, float]:
    """Per-axis mean physical ICI hops between logical ring neighbours.

    For each logical mesh axis, a ring collective (all-reduce psum /
    all-gather) sends every rank's payload to its +1 neighbour along
    that axis; this map reports how many physical torus links that
    neighbour step traverses on average under the mesh's curve
    embedding -- the hop term :class:`repro.tune.cost.CommSpec` weights
    modeled collective bytes by (DESIGN.md §15).

    ``mesh`` is a ``jax.sharding.Mesh`` (or anything with ``axis_names``
    and a ``shape`` mapping).  ``device_order`` defaults to the order
    the mesh was built under (:func:`mesh_device_order`); ``torus`` to
    the :func:`default_torus` of the per-pod chip count.  ``wrap=True``
    includes the last->first ring step.  The ``"pod"`` axis crosses DCN,
    not ICI: it is reported as 0.0 hops and excluded from the in-pod
    embedding (placement is per pod, as in
    :func:`make_production_mesh`).
    """
    from repro.core.schedule import grid_schedule

    names = tuple(mesh.axis_names)
    sizes = {a: int(mesh.shape[a]) for a in names}
    if device_order is None:
        device_order = mesh_device_order(mesh)
    if device_order not in DEVICE_ORDERS:
        raise ValueError(
            f"unknown device_order {device_order!r}; supported orders: "
            f"{', '.join(DEVICE_ORDERS)}")
    ici_axes = tuple(a for a in names if a != "pod")
    shape = tuple(sizes[a] for a in ici_axes)
    n = int(np.prod(shape)) if shape else 1
    out = {a: 0.0 for a in names}
    if n <= 1:
        return out
    rows, cols = torus or default_torus(n)
    if rows * cols != n:
        raise ValueError(
            f"torus {rows}x{cols} does not hold {n} in-pod chips")
    if device_order == "rowmajor":
        ranks = np.arange(n)
        coords = np.stack([ranks // cols, ranks % cols], axis=1)
    else:
        coords = np.asarray(grid_schedule(device_order, rows, cols))
    multi = np.stack(np.unravel_index(np.arange(n), shape), axis=1)
    for k, axis in enumerate(ici_axes):
        if shape[k] == 1:
            continue
        nxt = multi.copy()
        nxt[:, k] = (nxt[:, k] + 1) % shape[k]
        nbr = np.ravel_multi_index(tuple(nxt.T), shape)
        hops = _torus_hops(coords, coords[nbr], (rows, cols))
        if not wrap:
            hops = hops[multi[:, k] != shape[k] - 1]
        out[axis] = float(hops.mean()) if hops.size else 0.0
    return out


def _make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis *Auto*: the sharding rules in
    :mod:`repro.distributed.sharding` are GSPMD annotations, and jax's
    default Explicit axes would make every unannotated gather a type
    error instead of a propagated sharding."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axes))


def make_production_mesh(*, multi_pod: bool = False,
                         device_order: str = "rowmajor"):
    if device_order not in DEVICE_ORDERS:
        raise ValueError(
            f"unknown device_order {device_order!r}; supported orders: "
            f"{', '.join(DEVICE_ORDERS)}")
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    if device_order == "rowmajor":
        return _record_device_order(_make_mesh(shape, axes),
                                    device_order)
    devs = jax.devices()
    n = int(np.prod(shape))
    assert len(devs) >= n, (len(devs), n)
    per_pod = 256
    pods = shape[0] if multi_pod else 1
    rows, cols = default_torus(per_pod)
    ordered = []
    for p in range(pods):  # placement is per pod: DCN has no torus
        ordered += device_permutation(
            device_order, rows, cols, devs[p * per_pod:(p + 1) * per_pod])
    return _record_device_order(
        _make_mesh(shape, axes, ordered), device_order)


def make_smoke_mesh(shape=(2, 2, 2), axes=("pod", "data", "model"), *,
                    device_order: str = "rowmajor"):
    """Small mesh over ``jax.devices()``: the host devices of the CPU
    multi-device tests (8 for the default shape), or the chips of one
    TPU host (``chip_smoke.py --four-chips`` builds (2, 2) over four).

    ``device_order`` embeds the non-pod axes on the
    :func:`default_torus` of their chip count, same validation and
    permutation path as production."""
    if device_order == "rowmajor":
        return _record_device_order(_make_mesh(shape, axes),
                                    device_order)
    pods = shape[axes.index("pod")] if "pod" in axes else 1
    per_pod = int(np.prod(shape)) // pods
    rows, cols = default_torus(per_pod)
    devs = jax.devices()
    ordered = []
    for p in range(pods):
        ordered += device_permutation(
            device_order, rows, cols, devs[p * per_pod:(p + 1) * per_pod])
    return _record_device_order(
        _make_mesh(shape, axes, ordered), device_order)


def mesh_chips(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
