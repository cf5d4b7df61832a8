"""Production mesh builders.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state. With ``JAX_PLATFORMS=cpu`` the
dry-run forces 512 host devices (``launch/jax_setup.py``) before jax
starts; smoke tests and benches see the 1 real CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules
    here are GSPMD-style hints (``with_sharding_constraint``,
    ``shard_map`` specs) that assume the compiler may propagate
    shardings, which Explicit axes — ``jax.make_mesh``'s default —
    refuse."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips).

    Axis semantics: 'pod' = pure data parallelism across DCN; 'data' =
    in-pod data parallel / FSDP shard axis; 'model' = tensor/expert/
    sequence parallel axis (ICI).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_shard_mesh(n_shards: int):
    """1-D 'model' mesh for the sharded serving path (DESIGN.md §13):
    the tiers are row-partitioned over these devices and every policy
    lookup/write runs shard-local with a tiny candidate merge. On CPU
    pair with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    (set before the first jax import) — the launchers' ``--shards N``
    flag does exactly that when ``JAX_PLATFORMS=cpu``."""
    return make_mesh((n_shards,), ("model",))


def make_smoke_mesh(n_devices: int | None = None):
    """Tiny mesh over whatever devices exist (tests / examples)."""
    n = n_devices or len(jax.devices())
    model = 2 if n % 2 == 0 else 1
    return make_mesh((n // model, model), ("data", "model"))


def dp_axes(mesh) -> tuple:
    """Mesh axes used for batch/data parallelism."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
