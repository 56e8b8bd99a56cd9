"""Production meshes.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
initialization and only then calls these.
"""

from __future__ import annotations

import jax

from repro.sharding.rules import auto_axes


def _make_mesh(shape: tuple, axes: tuple):
    return auto_axes(jax.make_mesh(shape, axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU smoke tests / examples."""
    return _make_mesh((1, 1), ("data", "model"))


def make_node_mesh(num_devices: int | None = None):
    """1-D ``("data",)`` mesh over the host's devices — the mesh executor's
    default placement for the paper's K logical nodes (K must be a multiple
    of the device count; each device hosts K/ndev nodes)."""
    n = num_devices if num_devices is not None else len(jax.devices())
    return _make_mesh((n,), ("data",))


def make_multipod_mesh(num_pods: int | None = None, num_devices: int | None = None):
    """2-D ``("pod", "data")`` mesh over the host's devices — the multipod
    executor's default placement: the pod axis carries the expensive
    inter-pod tier, the data axis the cheap intra-pod reduction.  Defaults
    to 2 pods when the device count splits evenly, else 1 (every topology
    primitive degrades gracefully to a size-1 pod axis)."""
    n = num_devices if num_devices is not None else len(jax.devices())
    if num_pods is None:
        num_pods = 2 if n % 2 == 0 else 1
    if n % num_pods:
        raise ValueError(f"{n} devices do not split into {num_pods} pods")
    return _make_mesh((num_pods, n // num_pods), ("pod", "data"))


def batch_axes(mesh) -> tuple:
    """The axes that carry data parallelism (the paper's 'nodes')."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def data_axis_size(mesh) -> int:
    s = 1
    for a in batch_axes(mesh):
        s *= mesh.shape[a]
    return s
