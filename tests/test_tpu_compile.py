"""The serve path's kernels compile for a TPU v5e, at chip_smoke.py's
shapes: a 2^20-row static tier of width 64, its IVF layout (4096
clusters x 336-row bands, 512 probes), a 4096-slot dynamic tier, a
sealed segment of the segmented dynamic index, and the flat lookup
row-sharded over a 2x2 mesh.

Nothing runs: the TPU compiler compiles for a described ``v5e:2x2``
topology, which refuses what Mosaic would refuse on the chip (block
shapes off the (8, 128) tiling, too much VMEM) and programs that do not
fit. The topology is described inside a fixture — never at import — and
every test skips when it cannot be described.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

B, D, N = 32, 64, 1 << 20
K, CAP, NPROBE = 4096, 336, 512
DYN = 4096


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    c = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in c.as_text()       # the kernel, not a twin
    return c


def test_simsearch_compiles_at_full_tier(one_chip):
    from repro.kernels.simsearch.kernel import simsearch
    c = _compile(lambda q, x: simsearch(q, x, k=1),
                 _spec((B, D), jnp.float32, one_chip),
                 _spec((N, D), jnp.float32, one_chip))
    assert c.memory_analysis().argument_size_in_bytes >= N * D * 4


def test_ivf_scan_compiles_at_full_layout(one_chip):
    from repro.kernels.ivf_scan.kernel import ivf_scan_kernel
    _compile(lambda q, ci, co, sc, ri: ivf_scan_kernel(q, ci, co, sc, ri,
                                                       32),
             _spec((B, D), jnp.float32, one_chip),
             _spec((B, NPROBE), jnp.int32, one_chip),
             _spec((K, CAP, D), jnp.int8, one_chip),
             _spec((K, CAP), jnp.float32, one_chip),
             _spec((K, CAP), jnp.int32, one_chip))


@pytest.mark.parametrize("k, cap", [(8, 16), (8, 48)])
def test_ivf_scan_compiles_at_segment_layout(one_chip, k, cap):
    """A sealed segment of the segmented dynamic index: 64 and 256-row
    seals pack into 8 clusters x 16 / 48-row bands."""
    from repro.kernels.ivf_scan.kernel import ivf_scan_kernel
    _compile(lambda q, ci, co, sc, ri: ivf_scan_kernel(q, ci, co, sc, ri,
                                                       64),
             _spec((B, D), jnp.float32, one_chip),
             _spec((B, k), jnp.int32, one_chip),
             _spec((k, cap, D), jnp.int8, one_chip),
             _spec((k, cap), jnp.float32, one_chip),
             _spec((k, cap), jnp.int32, one_chip))


def test_fused_serve_compiles_at_full_layout(one_chip):
    from repro.kernels.fused_serve.kernel import fused_serve_kernel
    tile = 512
    _compile(lambda q, ci, co, sc, ri, dt, di: fused_serve_kernel(
                 q, ci, co, sc, ri, dt, di, 32, 16),
             _spec((B, D), jnp.float32, one_chip),
             _spec((B, NPROBE), jnp.int32, one_chip),
             _spec((K, CAP, D), jnp.int8, one_chip),
             _spec((K, CAP), jnp.float32, one_chip),
             _spec((K, CAP), jnp.int32, one_chip),
             _spec((DYN // tile, tile, D), jnp.bfloat16, one_chip),
             _spec((DYN // tile, tile), jnp.int32, one_chip))


def test_sharded_flat_lookup_compiles_on_2x2(topo):
    from repro.index.sharded import sharded_cosine_topk
    mesh = Mesh(np.array(topo.devices).reshape(4), ("model",),
                axis_types=(AxisType.Auto,))
    c = _compile(lambda q, x: sharded_cosine_topk(q, x, mesh, k=1,
                                                  force="pallas"),
                 _spec((B, D), jnp.float32, NamedSharding(mesh, P())),
                 _spec((N, D), jnp.float32,
                       NamedSharding(mesh, P("model", None))))
    # each chip holds a quarter of the tier
    assert c.memory_analysis().argument_size_in_bytes < N * D * 4 // 2
