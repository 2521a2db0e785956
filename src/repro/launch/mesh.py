"""Mesh construction — the single place device meshes are built.

Production (TPU v5e pods):
single-pod : (16, 16)    axes ("data", "model")        = 256 chips
multi-pod  : (2, 16, 16) axes ("pod", "data", "model") = 512 chips

Host meshes (tests / CPU-forced device counts):
make_host_mesh    : ("data", "model") over however many devices exist —
                    the P-sharded merge (kernels/fed_agg.*_sharded)
                    splits the flat model dim across every axis of it.
make_clients_mesh : 1-axis ("clients",) mesh the vectorized executor
                    shards the cohort (K) dim over (fl/executor.py).

Axis names come from the declared vocabulary in ``sharding/rules.py``
(``MESH_AXES``) — repro-lint's JAX004 rule keeps ad-hoc axis literals
out of shard_map / psum call sites.

Every mesh is built with ``AxisType.Auto`` axes: the FL path indexes
sharded arrays with plain gathers and slices (the executor's row
gather, the merge's ``[:P]`` un-pad), which explicit axes — the
``jax.make_mesh`` default since JAX 0.9 — refuse with
``ShardingTypeError``.

Functions (not module-level constants) so importing this module never
touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first init,
while smoke tests see the 1 real CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from ..sharding.rules import CLIENT_AXIS


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model: int = 1, data: int = 1):
    """Tiny mesh over however many (CPU) devices exist — used by tests."""
    n = len(jax.devices())
    model = min(model, n)
    data = max(1, min(data, n // model))
    return _mesh((data, model), ("data", "model"))


def make_clients_mesh(clients: int = 1):
    """1-axis ``("clients",)`` mesh for cohort-sharded local training.

    Clamps to however many devices exist, so asking for 8 on a
    single-device host yields a size-1 mesh — which the executor treats
    as "no mesh" (bitwise-inert fallback to the plain vmap path)."""
    n = max(1, min(int(clients), len(jax.devices())))
    return _mesh((n,), (CLIENT_AXIS,))
