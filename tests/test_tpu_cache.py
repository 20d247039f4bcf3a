"""Chip-scale SkyMemory placement (TPU torus adaptation)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.mapping import Strategy
from repro.core.tpu_cache import (
    TorusGrid,
    gather_cost_s,
    migrate_shards,
    row_major_layout,
    shard_layout_permutation,
    strategy_cost_table,
)


def test_torus_hops_wraparound():
    g = TorusGrid(16, 16)
    assert g.hops((0, 0), (15, 15)) == 2  # wraps both axes
    assert g.hops((0, 0), (8, 8)) == 16
    assert g.hops((3, 3), (3, 3)) == 0


def test_ring_layout_hop_monotone():
    g = TorusGrid(16, 16)
    center = (8, 8)
    layout = g.ring_layout(49, center)
    hops = [g.hops(center, p) for p in layout]
    assert hops[0] == 0
    assert hops == sorted(hops)  # BFS rings: non-decreasing hop distance


def test_ring_beats_row_major_worst_hops():
    g = TorusGrid(16, 16)
    center = (8, 8)
    ring = g.worst_hops(g.ring_layout(49, center), center)
    rm = g.worst_hops(row_major_layout(g, 49), center)
    assert ring < rm


def test_strategy_cost_table_ordering():
    """The paper's Fig-16 ordering holds at chip scale: ring placements
    gather in fewer worst-case hops than row-major."""
    g = TorusGrid(16, 16)
    costs = strategy_cost_table(g, num_shards=64, bytes_per_shard=1 << 20)
    assert costs["hop(bfs-rings)"] <= costs["rotation(row-major)"]
    assert costs["rotation_hop(boxed-rings)"] <= costs["rotation(row-major)"]


def test_gather_cost_includes_serialization():
    g = TorusGrid(4, 4)
    layout = g.ring_layout(4, (0, 0))
    small = gather_cost_s(g, layout, (0, 0), bytes_per_shard=0)
    big = gather_cost_s(g, layout, (0, 0), bytes_per_shard=int(50e9))
    assert big == pytest.approx(small + 1.0, rel=1e-3)


def test_shard_layout_permutation_valid():
    g = TorusGrid(8, 8)
    perm = shard_layout_permutation(g, 16, (4, 4), Strategy.ROTATION_HOP)
    assert len(set(perm.tolist())) == 16
    assert perm.min() >= 0 and perm.max() < 64


def test_migrate_shards_single_device_identity():
    # On a 1-device mesh the cyclic shift is the identity; the multi-device
    # path is exercised by the dry-run lowering (launch/dryrun.py).
    devs = np.array(jax.devices()[:1]).reshape(1)
    mesh = Mesh(devs, ("data",))
    x = jnp.arange(8.0).reshape(4, 2)
    y = migrate_shards(x, mesh, axis="data", shift=1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x))


def test_migrate_shards_lowering_multidevice():
    """lower() the migration collective against an abstract 4-device mesh."""
    mesh = jax.sharding.AbstractMesh((4,), ("data",))
    x = jax.ShapeDtypeStruct((8, 2), jnp.float32)

    def fn(v):
        return migrate_shards(v, mesh, axis="data", shift=1)

    lowered = jax.jit(fn).lower(x)
    text = lowered.as_text()
    assert "collective_permute" in text
    # full cyclic ring over the 4 shard positions
    assert "[[0, 1], [1, 2], [2, 3], [3, 0]]" in text
