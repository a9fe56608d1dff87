"""Production mesh construction — the canonical mesh entry points.

``make_host_mesh`` / ``make_production_mesh`` build the (data × model)
meshes the 2-D distribution planner (core/planner.py) reads its geometry
from; ``resolve_mesh`` turns the spec strings accepted by
``train.make_train_step`` / ``serving`` / ``repro.Database(mesh=...)``
into those meshes.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — jax locks the device count on
first backend initialization, and only dryrun.py is allowed to force the
512-placeholder-device configuration.

Every mesh is built by ``auto_mesh``: its axes are ``Auto``, so XLA's
partitioner propagates the planner's in_shardings through the step. (An
``Explicit`` axis, ``jax.make_mesh``'s default, makes sharding part of
the array type and refuses, for example, an einsum whose contracting
dims are sharded — exactly the co-partition plan.)
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.core.planner import DATA_AXIS_NAMES


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the one constructor of
    the meshes the planner compiles against."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod (TPU v5e); multi-pod adds a leading
    pod=2 axis (2 pods = 512 chips) used for data parallelism."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Small (data × model) mesh over however many devices this host
    exposes (tests; 8 virtual CPU devices on the tier1-spmd CI lane give
    a 4×2 mesh at ``model=2``). With a single visible device this falls
    back to a 1-axis mesh — the planner then reproduces its 1-D plans —
    instead of a degenerate (1, 1) mesh."""
    if model < 1:
        raise ValueError(f"make_host_mesh: model={model} must be >= 1")
    n = len(jax.devices())
    if n == 1 and model == 1:
        return auto_mesh((1,), ("model",))
    if n % model != 0:
        raise ValueError(
            f"make_host_mesh: {n} visible device(s) not divisible by "
            f"model={model}"
        )
    return auto_mesh((n // model, model), ("data", "model"))


def resolve_mesh(spec):
    """Resolve a mesh spec to a jax Mesh: None and Mesh objects pass
    through; the strings ``"host"``, ``"host:<model>"``, ``"production"``
    and ``"production:multipod"`` name the standard meshes above."""
    if spec is None or not isinstance(spec, str):
        return spec
    name, _, arg = spec.partition(":")
    if name == "host":
        return make_host_mesh(model=int(arg) if arg else 1)
    if name == "production":
        if arg and arg not in ("multipod", "multi_pod", "2"):
            raise ValueError(
                f"unknown production mesh variant {arg!r}; use "
                "'production' or 'production:multipod'"
            )
        return make_production_mesh(multi_pod=bool(arg))
    raise ValueError(
        f"unknown mesh spec {spec!r}; use 'host[:<model>]' or "
        "'production[:multipod]'"
    )


def batch_axes(mesh) -> tuple:
    """Mesh axes used for data parallelism — the fold the 2-D planner
    (``core.planner.DATA_AXIS_NAMES``) puts on batch dimensions."""
    return tuple(a for a in DATA_AXIS_NAMES if a in mesh.axis_names)


def data_parallel_size(mesh) -> int:
    """Total data-parallel ways: the product of the batch axes' sizes."""
    n = 1
    for a in batch_axes(mesh):
        n *= int(dict(mesh.shape)[a])
    return n
