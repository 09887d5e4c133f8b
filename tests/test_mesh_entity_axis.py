"""make_mesh composition over the ``data`` x ``entity`` axes — the mesh
the entity-sharded GAME step runs on. The ``entity`` axis previously had
no direct tier-1 coverage: these pin axis-order invariance, the
clear-error contract for infeasible axis sizes, and the entity-sharded
``device_put`` layout round trip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from photon_ml_tpu.parallel.mesh import make_mesh


def test_make_mesh_data_entity_composition():
    mesh = make_mesh({"data": 4, "entity": 2})
    assert mesh.shape == {"data": 4, "entity": 2}
    assert mesh.devices.size == 8
    assert len(set(d.id for d in mesh.devices.ravel())) == 8


def test_make_mesh_axis_order_invariance():
    """The same axis sizes in either order build meshes over the same
    device set with the same per-axis widths — a shard_map over
    P("entity") partitions identically either way."""
    m1 = make_mesh({"data": 4, "entity": 2})
    m2 = make_mesh({"entity": 2, "data": 4})
    assert dict(m1.shape) == {"data": 4, "entity": 2}
    assert dict(m2.shape) == {"entity": 2, "data": 4}
    assert (set(d.id for d in m1.devices.ravel())
            == set(d.id for d in m2.devices.ravel()))
    x = np.arange(16.0).reshape(8, 2)
    s1 = jax.device_put(jnp.asarray(x), NamedSharding(m1, P("entity")))
    s2 = jax.device_put(jnp.asarray(x), NamedSharding(m2, P("entity")))
    np.testing.assert_array_equal(np.asarray(s1), x)
    np.testing.assert_array_equal(np.asarray(s2), x)


def test_make_mesh_infeasible_axis_sizes_raise_clearly():
    """More mesh slots than devices must fail with the axis breakdown in
    the message, not a reshape traceback."""
    with pytest.raises(ValueError, match="devices"):
        make_mesh({"data": 64})
    with pytest.raises(ValueError, match="entity"):
        make_mesh({"data": 3, "entity": 3})  # 9 > 8 virtual devices
    with pytest.raises(ValueError, match="9 devices"):
        make_mesh({"data": 3, "entity": 3})


def test_make_mesh_rejects_nonpositive_axis():
    with pytest.raises(ValueError, match="entity"):
        make_mesh({"data": 4, "entity": 0})


def test_entity_sharded_device_put_layout_roundtrip():
    """An [E, ...] per-entity array laid out shard-by-entity on the mesh
    splits across exactly the entity axis and round-trips bit-exactly —
    the device boundary the sharded bucket solvers cross."""
    mesh = make_mesh({"data": 2, "entity": 4})
    E, D = 16, 3
    x = np.arange(E * D, dtype=np.float64).reshape(E, D)
    sharded = jax.device_put(jnp.asarray(x),
                             NamedSharding(mesh, P("entity")))
    np.testing.assert_array_equal(np.asarray(sharded), x)
    shards = sharded.addressable_shards
    assert len(shards) == 8
    # each entity-axis slice holds E/4 rows; the data axis replicates
    shapes = {s.data.shape for s in shards}
    assert shapes == {(E // 4, D)}
    rows_seen = sorted(int(s.index[0].start or 0) for s in shards)
    assert rows_seen == [0, 0, 4, 4, 8, 8, 12, 12]


def test_entity_axis_shard_map_sum_matches_host():
    """A no-collective shard_map over the entity axis (the bucket-solver
    pattern) computes the same per-entity results as the host."""
    from jax import shard_map

    mesh = make_mesh({"entity": 8})
    x = np.arange(32.0).reshape(8, 4)

    f = shard_map(lambda a: a * 2.0 + 1.0, mesh=mesh,
                  in_specs=(P("entity"),), out_specs=P("entity"),
                  check_vma=False)
    out = jax.jit(f)(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(out), x * 2.0 + 1.0)
