"""Collective primitives over a named mesh axis.

Design: the reference aggregates gradients with one collective call PER
PARAMETER TENSOR per step (codes/task2/dist_utils.py:39-49 — 8 tensors ⇒ 8
NCCL calls, SURVEY.md §3.2). Here every wrapper takes a whole pytree and
lowers to XLA collectives inside one jitted program, so XLA fuses/schedules
them over ICI; the per-parameter-loop overhead class disappears.

All functions must be called inside a ``shard_map``/``pmap`` context where
``axis_name`` is bound. Primitive coverage mirrors and extends what the
reference exercises (broadcast / all_reduce / all_gather, dist_utils.py:
33-49) plus the concepts its spec names (Reduce/Gather/Scatter,
sections/task2.tex:11) and the ring/all-to-all primitives that keep the door
open for sequence parallelism (SURVEY.md §5.7): psum, pmean, all_gather,
psum_scatter (= ReduceScatter), ppermute (ring shift), all_to_all.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

PyTree = Any


def axis_size(axis_name: str) -> int:
    """Static size of a bound mesh axis (a Python int at trace time)."""
    return lax.axis_size(axis_name)


def pmax_tree(tree: PyTree, axis_name: str) -> PyTree:
    """AllReduce-MAX over every leaf — the merge collective for online
    statistics (running softmax maxima, lse merges)."""
    return jax.tree.map(lambda x: lax.pmax(x, axis_name), tree)


def plogsumexp(x: jax.Array, axis_name: str) -> jax.Array:
    """Cross-shard log-sum-exp merge: each shard holds a partial
    ``lse_local = log Σ_local exp(s)`` over its slice of a reduced axis;
    the global lse is their logsumexp over the mesh axis. This is the
    SAME online combination rule the ring-attention fold uses per
    arriving block (tpudml/parallel/cp.py ``_merge_blocks``), expressed
    as one pmax + one psum — the shift makes the psum overflow-safe, and
    lse's shift-invariance makes ``stop_gradient`` on the shift exact:
    d lse/d lse_local = exp(lse_local − lse), the correct softmax slice
    weight, flows entirely through the psum term. Differentiable; used
    by the vocab-sharded fused cross-entropy head to merge per-shard
    partial-vocab statistics."""
    # stop_gradient on the INPUT, not the result: pmax has no JVP rule,
    # and with a symbolic-zero tangent the primitive is never
    # differentiated at all.
    m = lax.pmax(lax.stop_gradient(x), axis_name)
    return m + jnp.log(lax.psum(jnp.exp(x - m), axis_name))


def psum_tree(tree: PyTree, axis_name: str) -> PyTree:
    """AllReduce-SUM over every leaf of a pytree (one traced program)."""
    return jax.tree.map(lambda x: lax.psum(x, axis_name), tree)


def pmean_tree(tree: PyTree, axis_name: str) -> PyTree:
    """AllReduce-MEAN over every leaf."""
    return jax.tree.map(lambda x: lax.pmean(x, axis_name), tree)


def allreduce_average_gradients(grads: PyTree, axis_name: str = "data") -> PyTree:
    """Gradient aggregation, AllReduce strategy.

    Parity: reference ``allreduce_average_gradients`` — per-param
    ``all_reduce(SUM)`` then ``/world_size`` (codes/task2/dist_utils.py:
    39-42); here a single pmean over the grad pytree.
    """
    return pmean_tree(grads, axis_name)


def allgather_average_gradients(grads: PyTree, axis_name: str = "data") -> PyTree:
    """Gradient aggregation, AllGather strategy: gather every replica's
    gradient then average locally.

    Parity: reference ``allgather_average_gradients`` (codes/task2/
    dist_utils.py:44-49) — whose list-construction bug (``[zeros]*2``
    hardcodes world=2 and aliases one tensor) is deliberately NOT
    reproduced; SURVEY.md §2.1 calls for a *correct* allgather-mean.
    Mathematically equal to allreduce-mean; communication volume is
    world× larger — the comparison task2 asks students to measure
    (sections/checking.tex:20-21).
    """

    def gather_mean(g):
        stacked = lax.all_gather(g, axis_name)  # [world, ...]
        return jnp.mean(stacked, axis=0)

    return jax.tree.map(gather_mean, grads)


def reduce_scatter_average_gradients(grads: PyTree, axis_name: str = "data") -> PyTree:
    """Gradient aggregation, ReduceScatter(+AllGather) strategy.

    The bandwidth-optimal decomposition of AllReduce (what ring-allreduce
    does internally): psum_scatter leaves each replica with a distinct
    averaged shard, all_gather reassembles. Exposed as a third measurable
    strategy beyond the reference's two (sections/task2.tex:18 asks for ≥2
    collective primitives; this adds the Scatter/Reduce concepts named at
    task2.tex:11). Leading dim of each leaf must divide the axis size; falls
    back to pmean for leaves where it doesn't.
    """
    world = axis_size(axis_name)

    def rs_ag(g):
        if g.ndim >= 1 and g.shape[0] % world == 0:
            shard = lax.psum_scatter(g, axis_name, scatter_dimension=0, tiled=True)
            return lax.all_gather(shard, axis_name, axis=0, tiled=True) / world
        return lax.pmean(g, axis_name)

    return jax.tree.map(rs_ag, grads)


def all_gather_tree(tree: PyTree, axis_name: str, axis: int = 0, tiled: bool = False) -> PyTree:
    """AllGather every leaf along ``axis``."""
    return jax.tree.map(lambda x: lax.all_gather(x, axis_name, axis=axis, tiled=tiled), tree)


def psum_scatter_tree(tree: PyTree, axis_name: str, axis: int = 0) -> PyTree:
    """ReduceScatter every leaf along ``axis`` (tiled)."""
    return jax.tree.map(
        lambda x: lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True), tree
    )


def broadcast_from(tree: PyTree, axis_name: str, root: int = 0) -> PyTree:
    """Broadcast every leaf from replica ``root`` to all replicas.

    Parity: reference ``init_parameters`` — per-param ``dist.broadcast(p, 0)``
    (codes/task2/dist_utils.py:33-37). Implemented as select-root + psum,
    which XLA lowers to an efficient one-to-all over ICI. In idiomatic JAX
    this is rarely needed (replicated init from a shared PRNG seed gives
    bitwise-identical params on every replica for free — the design the DP
    engine uses by default); provided for explicit-broadcast parity and for
    resume-from-checkpoint flows (SURVEY.md §5.4).
    """

    def bcast(x):
        idx = lax.axis_index(axis_name)
        masked = jnp.where(idx == root, x, jnp.zeros_like(x))
        return lax.psum(masked, axis_name)

    return jax.tree.map(bcast, tree)


def ppermute_ring(x: jax.Array, axis_name: str, shift: int = 1) -> jax.Array:
    """Ring shift: replica i's value goes to replica (i+shift) mod world.

    The primitive under ring-allreduce and ring attention (SURVEY.md §5.7
    scope note: exposed so the SP door stays open).
    """
    world = axis_size(axis_name)
    perm = [(i, (i + shift) % world) for i in range(world)]
    return lax.ppermute(x, axis_name, perm)


def all_to_all(x: jax.Array, axis_name: str, split_axis: int, concat_axis: int) -> jax.Array:
    """All-to-all: transpose a sharded axis with a local axis (the Ulysses
    sequence-parallel primitive; SURVEY.md §5.7)."""
    return lax.all_to_all(x, axis_name, split_axis, concat_axis, tiled=True)


AGGREGATORS = {
    "allreduce": allreduce_average_gradients,
    "allgather": allgather_average_gradients,
    "reducescatter": reduce_scatter_average_gradients,
}


def get_aggregator(name: str):
    """Factory keyed by the config's ``aggregation`` field (task2's ≥2
    collective-primitive contract, sections/task2.tex:18)."""
    try:
        return AGGREGATORS[name.lower()]
    except KeyError:
        raise ValueError(
            f"unknown aggregation {name!r}; options: {sorted(AGGREGATORS)}"
        ) from None
