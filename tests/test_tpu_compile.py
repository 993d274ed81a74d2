"""Compile the main path's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler builds for a topology that is
described, not attached, and refuses what the chip would refuse (block
shapes that do not tile, layouts Mosaic cannot match).  Interpret-mode
tests cannot see those faults.  The topology is described inside a
fixture, never at import, so every pytest worker collects the same tests
and only the worker that runs this file loads the TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels.kmeans_assign.ops import assign_with_dist
from repro.obs.prof import parse_collectives


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh_2x2(topo):
    return Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


def _hlo(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _native(x, c):
    return assign_with_dist(x, c, interpret=False)


@pytest.mark.parametrize("n_edges,n,d,k", [
    (16, 128, 64, 3),      # the local block: per-edge minibatch, vmapped
    (16, 128, 64, 8),
])
def test_kmeans_assign_compiles_vmapped_over_edges(one_chip, n_edges, n, d,
                                                   k):
    x = jax.ShapeDtypeStruct((n_edges, n, d), jnp.float32,
                             sharding=one_chip)
    c = jax.ShapeDtypeStruct((n_edges, k, d), jnp.float32,
                             sharding=one_chip)
    assert "tpu_custom_call" in _hlo(jax.vmap(_native), x, c)


@pytest.mark.parametrize("n,d,k", [
    (20000, 64, 3),        # the paper's full dataset, padded to the block
    (4096, 64, 3),
    (128, 64, 3),
])
def test_kmeans_assign_compiles_whole_dataset(one_chip, n, d, k):
    x = jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=one_chip)
    c = jax.ShapeDtypeStruct((k, d), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _hlo(_native, x, c)


def test_kmeans_sweep_scorer_compiles_over_the_cell_axis(one_chip,
                                                          monkeypatch):
    """``KMeans.evaluate_many``'s compiled call at the sweep cell's
    shapes (63 cells of [3, 64] centers against the 4,000-row eval set,
    unbatched) runs the kernel natively, at the cell's ``highest``."""
    from repro.config import get_config
    from repro.kernels.kmeans_assign import ops as ka_ops
    from repro.models import build_model
    monkeypatch.setattr(ka_ops, "interpret_default", lambda: False)
    model = build_model(get_config("kmeans-traffic").model, impl="pallas")
    centers = {"centers": jax.ShapeDtypeStruct((63, 3, 64), jnp.float32,
                                               sharding=one_chip)}
    x = jax.ShapeDtypeStruct((4000, 64), jnp.float32, sharding=one_chip)
    prev = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        hlo = model._score_many.lower(centers, x).compile().as_text()
    finally:
        jax.config.update("jax_default_matmul_precision", prev)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (2, 4096, 64, 64, 128, 256),   # granite-4.0-h-micro's Mamba-2 layer
    (1, 512, 32, 64, 128, 128),    # mamba2-370m's heads
])
def test_ssd_scan_compiles_at_published_widths(one_chip, b, s, h, p, n,
                                               chunk):
    """The SSD kernel's forward at a model's widths, under the float32
    ``high`` default a configuration may set (the kernel takes its own
    products at ``highest``)."""
    from repro.kernels.ssd_scan.kernel import ssd_fwd

    def sd(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    prev = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "high")
    try:
        hlo = _hlo(lambda x, da, bm, cm: ssd_fwd(x, da, bm, cm, chunk),
                   sd(b, s, h, p), sd(b, s, h), sd(b, s, n), sd(b, s, n))
    finally:
        jax.config.update("jax_default_matmul_precision", prev)
    assert "tpu_custom_call" in hlo


def test_collective_census_reads_tpu_hlo(mesh_2x2):
    """The contracts' census parses the TPU compiler's HLO, whose result
    types carry tiled layouts: an edge stack sharded over ``data`` and
    gathered before the cross-edge sum shows its all-gather and no
    all-reduce (gather-before-reduce, as the sharded EL programs do)."""
    def aggregate(stack):
        full = lax.with_sharding_constraint(stack,
                                            NamedSharding(mesh_2x2, P()))
        return full.sum(axis=0)

    stack = jax.ShapeDtypeStruct((16, 59, 8), jnp.float32,
                                 sharding=NamedSharding(mesh_2x2, P("data")))
    census = parse_collectives(_hlo(aggregate, stack))["per_op"]
    assert census["all-gather"]["count"] >= 1
    assert "all-reduce" not in census
