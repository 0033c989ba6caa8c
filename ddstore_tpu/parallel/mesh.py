"""Device-mesh and sharding helpers.

The store's unit of distribution is the host process (one shard per
TPU-VM host); the unit of compute distribution is the device mesh. These
helpers build the meshes the rest of the framework assumes:

* ``dp`` — data parallel (batch dimension; the reference's only strategy,
  via torch DDP, SURVEY §2.2),
* ``tp`` — tensor parallel (model dims),
* ``sp`` — sequence/context parallel (ring attention),
* ``pp`` — pipeline stages,
* ``ep`` — expert parallel (MoE routing).

Axes the caller does not ask for are simply absent — XLA sees only the
mesh it is given.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS_ORDER = ("pp", "dp", "ep", "sp", "tp")  # outer→inner; tp innermost so
# tensor-parallel collectives ride the fastest ICI links.


def make_mesh(axes: Dict[str, int],
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a Mesh with the given axis sizes, e.g. ``{"dp": 4, "tp": 2}``.

    Axis order follows AXIS_ORDER so that tensor-parallel groups map to
    adjacent devices (fastest links), data-parallel groups to the outer
    dimension — the standard TPU layout recipe.
    """
    if devices is None:
        devices = jax.devices()
    names = [a for a in AXIS_ORDER if a in axes]
    extra = set(axes) - set(names)
    if extra:
        names += sorted(extra)
    sizes = [axes[a] for a in names]
    n = int(np.prod(sizes)) if sizes else 1
    if n > len(devices):
        raise ValueError(f"mesh wants {n} devices, have {len(devices)}")
    dev = np.asarray(devices[:n]).reshape(sizes)
    return Mesh(dev, tuple(names))


def data_parallel_mesh(n: Optional[int] = None) -> Mesh:
    """1-D dp mesh over (up to) all devices."""
    devs = jax.devices()
    n = len(devs) if n is None else n
    return make_mesh({"dp": n}, devs)


def local_mesh() -> Mesh:
    """Mesh over this process's addressable devices only (one ICI island /
    one host) — the device-side analogue of a replica group."""
    return make_mesh({"dp": len(jax.local_devices())}, jax.local_devices())


def batch_sharding(mesh: Mesh, axis: str = "dp") -> NamedSharding:
    """Sharding for a batch: leading dim split over `axis`, rest replicated."""
    return NamedSharding(mesh, P(axis))


def replicate(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
