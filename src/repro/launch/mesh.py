"""Production mesh definition.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state. The single-pod mesh is 16×16 = 256 chips
(data × model); the multi-pod mesh adds a leading pod axis (2 pods = 512
chips). Batch-like dimensions shard over ("pod","data"); tensor-parallel
dimensions over "model" (intra-pod ICI); only data-parallel gradient
reductions cross the pod boundary (DCI) — the standard hierarchy.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    # Auto axes: shardings come from the rules' PartitionSpecs and GSPMD
    # (jax.make_mesh defaults to Explicit axes)
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_serve_mesh(tp: int | None = None):
    """Serving mesh ``(data=1, model=tp)`` over the first ``tp`` of this
    process's devices (``jax.devices()``): the chips of a TPU host, or CPU
    devices elsewhere.  A CPU process shows more than one device only when
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` is set before jax
    initializes its backend — the way tests exercise tp > 1 without chips.
    """
    tp = 1 if tp is None else int(tp)
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    n = jax.device_count()
    if tp > n:
        platform = jax.default_backend()
        hint = (
            f"; set XLA_FLAGS=--xla_force_host_platform_device_count={tp} "
            f"before the process starts" if platform == "cpu" else ""
        )
        raise ValueError(
            f"tp={tp} needs {tp} devices but only {n} {platform} "
            f"device(s) are visible{hint}"
        )
    return _make_mesh((1, tp), ("data", "model"))


def mesh_name(mesh) -> str:
    return "x".join(f"{mesh.shape[a]}" for a in mesh.axis_names)
