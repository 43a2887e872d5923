"""The query path compiled for a TPU v5e chip that is described, not
attached: what the chip's compiler would refuse fails here, at no chip
time.

The topology is described inside a module-scoped fixture (never at
import), because one process at a time may load the TPU library; all
compiles for the chip stay in this one file.  Shapes are those of
``chip_smoke.py``'s deployment.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import backend as B
from repro.core import relational as R
from repro.core.capacity import BuildCaps
from repro.core.distributed import sharded_run_plan
from repro.core.index import build_index_arrays
from repro.core.paths import DeviceGraph
from repro.core.sharded_index import (ShardedIndexArrays, _SHARDED_FIELDS,
                                      index_specs, shard_capacity)

#: One v5e chip's HBM (Google Cloud, "TPU v5e": 16 GB).
HBM_BYTES = 16 * 10**9

# chip_smoke.py's deployment: gmark_citation(20_000, avg_degree=8,
# seed=0) at k = 2 — its edge count (inverse closure included) and the
# build capacities the host estimator gives it — with the largest query
# capacities its one-chip run dispatched (pair cap 2 Mi, join cap 4 Mi).
N_VERTICES = 20_000
N_EDGES = 159_692
BUILD_CAPS = BuildCaps(level_rows=(1 << 18, 1 << 23), pair_cap=1 << 23,
                       union_pair_cap=1 << 23, seq_rows=1 << 23,
                       l2c_rows=1 << 23, n_seqs=128)
QUERY_CAPS = B.QueryCaps(class_cap=1 << 16, pair_cap=1 << 21,
                         join_cap=1 << 22)
#: Fig. 5 "TC" at k = 2: ((l1 . l2) & l3) . (l4 . l5) — a class-space
#: conjunction, two materializations and a join.
TC_SHAPE = ("join", ("conj", ("lookup", 1), ("lookup", 1)), ("lookup", 1))
#: The implementation core/backend.py states for each scoped operator.
STATED = {"conj_classes": "xla", "materialize": "xla"}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_cache(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo, no_cache):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo, no_cache):
    """The ``engine`` mesh axis over the four described chips."""
    return Mesh(np.array(topo.devices[:4]), ("engine",))


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _fits_one_chip(compiled) -> int:
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert total < HBM_BYTES, total
    return total


def _index_shapes():
    """Shapes of chip_smoke's device index (nothing is built)."""
    i32 = jax.ShapeDtypeStruct((N_EDGES,), jnp.int32)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    dg = DeviceGraph(R.Relation((i32, i32, i32), scalar, scalar),
                     N_VERTICES, 6)
    return jax.eval_shape(
        lambda d: build_index_arrays(d, 2, BUILD_CAPS.key()), dg)


def _sharded_shapes(arrays, mesh, axis: str = "engine"):
    """Shapes of ``shard_index`` of that index over ``mesh``, each leaf
    with the NamedSharding ``place_index`` gives it."""
    n = int(dict(mesh.shape)[axis])
    cap = shard_capacity(arrays.c2p_v.shape[0], n)
    shapes = {}
    for f in ShardedIndexArrays._fields:
        if f in ("pair_counts", "c2p_counts"):
            shapes[f] = (n,)
        elif f == "class_starts":
            shapes[f] = (n,) + arrays.class_starts.shape
        elif f in _SHARDED_FIELDS:
            shapes[f] = (n, cap)
        else:
            shapes[f] = getattr(arrays, f).shape
    return ShardedIndexArrays(*[
        jax.ShapeDtypeStruct(shapes[f], jnp.int32,
                             sharding=NamedSharding(mesh, spec))
        for f, spec in zip(ShardedIndexArrays._fields, index_specs(axis))])


def test_plan_walker_compiles_for_v5e(one_chip):
    """The vmapped LocalBackend walker of a conjunction-and-join plan, at
    chip_smoke's index and query capacities, compiles for the chip, fits
    its HBM, and compiles each scoped operator to its stated
    implementation — a Pallas kernel would show as ``tpu_custom_call``,
    one lowered in interpret mode would not."""
    arrays = _index_shapes()
    ranges = jax.ShapeDtypeStruct((1, 3, 2), jnp.int32)
    compiled = B.run_plan_batch.lower(
        _on(one_chip, arrays), TC_SHAPE, QUERY_CAPS, N_VERTICES,
        _on(one_chip, ranges)).compile()
    text = compiled.as_text()
    assert B.op_implementations(text) == STATED
    assert ("tpu_custom_call" in text) == ("pallas" in STATED.values())
    assert _fits_one_chip(compiled) > BUILD_CAPS.pair_cap * 4


def test_sharded_walker_compiles_for_v5e_2x2(four_chips):
    """The ShardedBackend walker of the same plan — one ``shard_map`` over
    the four chips of a v5e 2x2, its index leaves placed by NamedSharding
    — compiles for those chips with its all-to-all exchanges and
    overflow reduction, fits each chip's HBM, and keeps the stated
    implementations."""
    arrays = _index_shapes()
    sharded = _sharded_shapes(arrays, four_chips)
    ranges = jax.ShapeDtypeStruct((3, 2), jnp.int32,
                                  sharding=NamedSharding(four_chips, P()))
    fn = sharded_run_plan(four_chips, "engine", N_VERTICES, TC_SHAPE,
                          QUERY_CAPS)
    compiled = fn.lower(sharded, ranges).compile()
    text = compiled.as_text()
    assert "all-to-all" in text
    assert B.op_implementations(text) == STATED
    # memory_analysis counts one device's share of the sharded program
    assert _fits_one_chip(compiled) > sharded.c2p_v.shape[1] * 4


def test_pallas_kernel_lowers_to_mosaic_on_tpu(one_chip, monkeypatch):
    """With the backend a TPU, a Pallas kernel of this repository lowers
    to a Mosaic ``tpu_custom_call`` — never to the interpreter."""
    from repro.kernels import fingerprint

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()  # no trace from an interpret-mode lowering
    col = jax.ShapeDtypeStruct((1 << 20,), jnp.int32, sharding=one_chip)
    compiled = fingerprint.fingerprint_rows.lower((col, col), salt=3).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_one_chip(compiled)
    jax.clear_caches()


def test_interpret_lowering_has_no_custom_call(one_chip):
    """The same kernel lowered in interpret mode (the CPU switch) holds no
    ``tpu_custom_call``: the check above tells the two apart."""
    from repro.kernels import fingerprint

    jax.clear_caches()
    col = jax.ShapeDtypeStruct((4096,), jnp.int32, sharding=one_chip)
    text = fingerprint.fingerprint_rows.lower((col,), salt=1).compile().as_text()
    assert "tpu_custom_call" not in text
    jax.clear_caches()
