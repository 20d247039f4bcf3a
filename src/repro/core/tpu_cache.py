"""SkyMemory placement math applied to the TPU ICI torus (beyond-paper).

A TPU v5e pod is a 2D ICI torus -- the same +GRID abstraction the paper
assumes for satellites.  This module reuses the paper's chunk-placement and
migration machinery at chip scale:

* *chunk striping*  -> sequence-dim sharding of the paged KV cache across the
  ``data`` mesh axis (each device holds ``1/n`` of the context blocks);
* *hop-aware placement* -> assigning logical cache shards to mesh positions
  in BFS rings around the decode host so a gather touches the fewest ICI
  hops (``ring_layout``);
* *rotation migration* -> ``lax.ppermute`` shifting shards one position
  along the torus (``migrate_shards``), the collective-permute analogue of
  the paper's per-plane parallel chunk moves;
* the paper's worst-case latency estimator with TPU constants
  (``gather_cost_s``): ~1 us/link hop, 50 GB/s/link ICI.

Used by the ``long_500k`` decode path (context-sharded KVC) and by the
roofline/benchmark layers.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.mapping import Strategy, _bfs_offsets

ICI_HOP_LATENCY_S = 1e-6          # per-hop ICI latency (order of magnitude)
ICI_LINK_BW_BYTES_S = 50e9        # ~50 GB/s per ICI link


@dataclass(frozen=True)
class TorusGrid:
    """A 2D device torus (rows x cols) -- chip-scale +GRID."""

    rows: int
    cols: int

    @property
    def size(self) -> int:
        return self.rows * self.cols

    def hops(self, a: tuple[int, int], b: tuple[int, int]) -> int:
        dr = abs(a[0] - b[0])
        dc = abs(a[1] - b[1])
        return min(dr, self.rows - dr) + min(dc, self.cols - dc)

    def ring_layout(
        self, num_shards: int, center: tuple[int, int] = (0, 0),
        strategy: Strategy = Strategy.HOP,
    ) -> list[tuple[int, int]]:
        """Positions for logical shards 0..n-1, BFS rings around ``center``.

        The same traversal that reproduces the paper's Figs 14-15, so shard 0
        sits on the host chip and shard *i*'s hop distance grows ~sqrt(i).
        """
        if num_shards > self.size:
            raise ValueError("more shards than devices")
        bound = None
        if strategy is Strategy.ROTATION_HOP:
            side = int(math.ceil(math.sqrt(num_shards)))
            bound = (side, side)
        offs = _bfs_offsets(num_shards, bound=bound, torus=(self.cols, self.rows))
        return [
            ((center[0] + ds) % self.rows, (center[1] + dp) % self.cols)
            for dp, ds in offs
        ]

    def worst_hops(self, layout: list[tuple[int, int]], center: tuple[int, int]) -> int:
        return max((self.hops(center, pos) for pos in layout), default=0)


def gather_cost_s(
    grid: TorusGrid,
    layout: list[tuple[int, int]],
    center: tuple[int, int],
    bytes_per_shard: int,
) -> float:
    """Paper Eq-3-style worst-case fetch estimate with TPU ICI constants.

    Per-shard fetch = hop latency x hops + serialization over the last link;
    all shards move in parallel (paper: chunks queried in parallel), so the
    gather cost is the max.
    """
    per = [
        grid.hops(center, pos) * ICI_HOP_LATENCY_S
        + bytes_per_shard / ICI_LINK_BW_BYTES_S
        for pos in layout
    ]
    return max(per, default=0.0)


def row_major_layout(grid: TorusGrid, num_shards: int) -> list[tuple[int, int]]:
    """The rotation-aware (Fig 13) baseline layout at chip scale."""
    if num_shards > grid.size:
        raise ValueError("more shards than devices")
    return [(i // grid.cols, i % grid.cols) for i in range(num_shards)]


# ---------------------------------------------------------------------------
# JAX pieces: sharded paged-KVC container + ppermute migration.
# ---------------------------------------------------------------------------

def kvc_sharding(
    mesh: Mesh,
    *,
    seq_axis: str = "data",
    head_axis: str = "model",
) -> NamedSharding:
    """Sharding for a paged KV cache [n_blocks, block, kv_heads, head_dim]:
    context blocks striped over ``seq_axis`` (the paper's chunk striping),
    KV heads over ``head_axis`` (tensor parallel)."""
    return NamedSharding(mesh, P(seq_axis, None, head_axis, None))


def migrate_shards(x: jax.Array, mesh: Mesh, *, axis: str = "data", shift: int = 1):
    """Rotation migration at chip scale: cyclically shift cache shards
    ``shift`` positions along ``axis`` with a collective permute.

    The leading dim of ``x`` must be sharded over ``axis``.  Mirrors the
    paper's §3.4 parallel per-plane migration: every device forwards its
    shard to the next position in one collective step.
    """
    n = mesh.shape[axis]
    perm = [(i, (i + shift) % n) for i in range(n)]

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=P(axis),
        out_specs=P(axis),
    )
    def _shift(shard):
        return lax.ppermute(shard, axis_name=axis, perm=perm)

    return _shift(x)


def strategy_cost_table(
    grid: TorusGrid, num_shards: int, bytes_per_shard: int,
    center: tuple[int, int] | None = None,
) -> dict[str, float]:
    """Compare the paper's placements as chip-scale gather costs."""
    if center is None:
        center = (grid.rows // 2, grid.cols // 2)
    layouts = {
        "rotation(row-major)": row_major_layout(grid, num_shards),
        "hop(bfs-rings)": grid.ring_layout(num_shards, center, Strategy.HOP),
        "rotation_hop(boxed-rings)": grid.ring_layout(
            num_shards, center, Strategy.ROTATION_HOP
        ),
    }
    return {
        name: gather_cost_s(grid, layout, center, bytes_per_shard)
        for name, layout in layouts.items()
    }


def device_grid_for_mesh(mesh: Mesh, axes: tuple[str, str] = ("data", "model")) -> TorusGrid:
    return TorusGrid(rows=mesh.shape[axes[0]], cols=mesh.shape[axes[1]])


def shard_layout_permutation(
    grid: TorusGrid, num_shards: int, center: tuple[int, int],
    strategy: Strategy = Strategy.ROTATION_HOP,
) -> np.ndarray:
    """Permutation p where logical shard i lives at flat device index p[i]."""
    layout = grid.ring_layout(num_shards, center, strategy)
    return np.array([r * grid.cols + c for r, c in layout], dtype=np.int32)
