"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax init,
and smoke tests/benches must keep seeing 1 device.
"""
from __future__ import annotations

import jax


def _mk(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 (512 chips, 2 pods).

    Axes: pod = cross-pod data parallel (DCN), data = in-pod data parallel
    (+ FSDP shard axis), model = tensor/expert/table parallel (ICI).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh_for(n_devices: int, *, model: int = 1):
    """Dev/test helper: (data, model) mesh over whatever devices exist."""
    assert n_devices % model == 0
    return _mk((n_devices // model, model), ("data", "model"))


def parse_mesh_arg(spec: str | None):
    """``--mesh data=N`` -> Mesh (or None for N==1 / no flag).

    N==1 maps to None on purpose: mesh-less is the exact pre-mesh code
    path (no sharded jit, no placement), so a default launch stays
    bit-for-bit what it was.  Requires the process to actually have N
    devices — on CPU set ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    before any jax import.
    """
    if spec is None:
        return None
    try:
        axis, n = spec.split("=")
        n = int(n)
    except ValueError:
        raise SystemExit(f"--mesh expects AXIS=N (e.g. data=8), got {spec!r}")
    if axis != "data":
        raise SystemExit(f"--mesh supports only the data axis, got {axis!r}")
    if n <= 1:
        return None
    have = jax.device_count()
    if have < n:
        raise SystemExit(
            f"--mesh data={n} but only {have} device(s) visible; on CPU "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count={n}")
    return make_mesh_for(n)
