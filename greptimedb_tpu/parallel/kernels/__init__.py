"""Pallas TPU kernels for the cross-shard combine paths.

The sharded query path moves cross-shard state through `gather_blocks`
+ host-ordered folds. The kernels here keep that state where the
reduction runs:

- ring_fold    — hash-groupby shuffle: the blocked cross-shard group
  fold as a sequential ring (2(ns-1) neighbor hops of the (g, nb)
  accumulator) instead of an all_gather of every shard's partial
  blocks, folding in the canonical FOLD_BLOCKS left-fold order so the
  bit-identity contract across mesh 1/2/4/8 holds by construction.
- topk_merge   — distributed topk: per-shard candidate heaps merged
  pairwise around the ring by a merge-path k-selection kernel instead
  of all-gathering ns*k candidates to every shard.

Kernel selection is planner-driven (query/planner.decide_kernel — the
`kernel=pallas|xla` dimension of decide_mesh_execution). There is ONE
body per kernel: Mosaic compiles it on a TPU backend and the Pallas
interpreter runs the same body elsewhere (`interpret=` threaded from
base.interpret_mode), so tier-1 under JAX_PLATFORMS=cpu exercises
exactly what the chip runs and the mesh-parity fuzz asserts
bit-identity against the XLA path. The hops between shards are
`ppermute` (ICI collective-permute) in both cases.
"""

from greptimedb_tpu.parallel.kernels.base import (
    interpret_mode,
    kernel_mode,
    kernels_enabled,
    native_available,
    ring_comm_bytes,
    sequential_ring,
)
from greptimedb_tpu.parallel.kernels.ring_fold import RingFoldCtx
from greptimedb_tpu.parallel.kernels.topk_merge import (
    ring_topk_merge,
    topk_comm_bytes,
)

__all__ = [
    "RingFoldCtx",
    "interpret_mode",
    "kernel_mode",
    "kernels_enabled",
    "native_available",
    "ring_comm_bytes",
    "ring_topk_merge",
    "sequential_ring",
    "topk_comm_bytes",
]
