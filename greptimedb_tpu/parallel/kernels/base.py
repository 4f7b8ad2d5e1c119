"""Kernel availability, interpret-mode threading, and the ring harness.

jax imports stay inside functions: the planner asks this module for the
kernel mode in processes that must not pay a jax import for it.
"""

from __future__ import annotations


def native_available() -> bool:
    """True when the Mosaic TPU compiler is behind pallas_call."""
    import jax

    return jax.default_backend() == "tpu"


def kernel_mode(opts) -> str:
    """The `[mesh] pallas_kernels` knob value ("auto"|"on"|"off") of a
    MeshOptions (or anything shaped like one; None -> "auto")."""
    mode = getattr(opts, "pallas_kernels", "auto") if opts is not None \
        else "auto"
    return mode if mode in ("auto", "on", "off") else "auto"


def kernels_enabled(opts) -> bool:
    """Should kernel program variants be considered at all? auto =
    native TPU backend only; on = everywhere, riding interpret mode off
    TPU (tests, the parity fuzz, CPU bench); off = never."""
    mode = kernel_mode(opts)
    if mode == "off":
        return False
    if mode == "on":
        return True
    return native_available()


def interpret_mode() -> bool:
    """`interpret=` value for every pallas_call in this package,
    threaded from the mesh config via the planner decision (gtlint
    GT022 rejects hard-coded literals): interpret exactly when the
    backend has no Mosaic compiler, so CPU tier-1 runs the real kernel
    bodies under the Pallas interpreter."""
    return not native_available()


def ring_comm_bytes(ns: int, plane_bytes: int) -> int:
    """Estimated inter-chip bytes of one sequential ring pass: the
    accumulator (plane_bytes) crosses 2(ns-1) neighbor hops — (ns-1)
    for the fold phase, (ns-1) for the latch broadcast."""
    return max(0, 2 * (int(ns) - 1)) * int(plane_bytes)


def sequential_ring(local, combine, ns: int, axis_name: str | None = None):
    """Sequential reduce-then-broadcast ring over `ns` shards.

    `local` (a pytree of per-shard arrays) is shard 0's seed
    accumulator; at hop s the accumulator moves to the right neighbor
    and shard s latches `combine(acc)` (its local contribution folded
    onto the prefix of shards 0..s-1). After ns-1 hops shard ns-1
    holds the total; ns-1 more hops broadcast it, each shard latching
    the value the moment it passes by. The combine order is therefore
    EXACTLY shard 0..ns-1 sequential — the same left fold the
    gather_blocks + left_fold_sum path runs — so results are
    bit-identical to the all-gather path by construction, while only
    2(ns-1) accumulator-sized messages cross the interconnect instead
    of (ns-1) full partial sets per shard.

    The latches are jnp.where selects (no arithmetic — a select never
    flips -0.0 or perturbs NaN payloads). ppermute is the hop
    primitive: on TPU it lowers to the ICI collective-permute (an
    async remote copy between neighbors).
    """
    import jax
    import jax.numpy as jnp

    if axis_name is None:
        from greptimedb_tpu.parallel.mesh import AXIS_SHARD

        axis_name = AXIS_SHARD
    tree = jax.tree_util.tree_map
    my = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % ns) for i in range(ns)]

    def hop(t):
        return tree(lambda a: jax.lax.ppermute(a, axis_name, perm), t)

    def latch(cond, new, old):
        return tree(lambda a, b: jnp.where(cond, a, b), new, old)

    acc = local
    for s in range(1, ns):
        acc = hop(acc)
        acc = latch(my == s, combine(acc), acc)
    result = latch(my == ns - 1, acc, tree(jnp.zeros_like, acc))
    for t in range(ns - 1):
        acc = hop(acc)
        result = latch(my == t, acc, result)
    return result
