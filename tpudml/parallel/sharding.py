"""Sharding utilities over a named device mesh.

The thin layer every parallel engine shares: NamedSharding constructors,
host→mesh placement helpers, and a ``shard_map`` wrapper with this repo's
defaults.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any


def shard_map_fn(fn, mesh: Mesh, in_specs, out_specs, check_rep: bool = False):
    """``shard_map`` with this repo's defaults (rep-check off: collective
    aggregation intentionally produces replicated outputs from sharded
    inputs, which the static replication checker can't always verify)."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check_rep
    )


@dataclasses.dataclass(frozen=True)
class KernelLayout:
    """How a GSPMD engine lays activations over its mesh: the axis that
    splits the batch dim and the axis that splits attention heads (None =
    not split). The SPMD partitioner cannot partition a Pallas kernel
    ("Mosaic kernels cannot be automatically partitioned"), so ops backed
    by one run per shard under this layout (:func:`per_shard`)."""

    mesh: Mesh
    batch: str | None = None
    head: str | None = None


_KERNEL_LAYOUT: contextvars.ContextVar[KernelLayout | None] = (
    contextvars.ContextVar("tpudml_kernel_layout", default=None)
)


@contextlib.contextmanager
def kernel_layout(mesh: Mesh, batch: str | None = None,
                  head: str | None = None):
    """Declare the activation layout while a GSPMD step is traced."""
    token = _KERNEL_LAYOUT.set(KernelLayout(mesh, batch, head))
    try:
        yield
    finally:
        _KERNEL_LAYOUT.reset(token)


def per_shard(fn, in_roles, out_roles):
    """``fn`` as is when no :class:`KernelLayout` is active (single device,
    or already inside an engine's ``shard_map``); otherwise ``fn`` run per
    shard in a ``shard_map`` over the layout's mesh. ``in_roles`` gives,
    per operand, a tuple naming each dim ``"batch"``, ``"head"`` or None;
    ``out_roles`` likewise for the output (a tuple of such tuples for
    several outputs). Dims split over no axis compute replicated, and
    shard_map's transpose (psum of replicated operands' cotangents,
    cotangents of replicated outputs divided by the axis size) keeps the
    gradients those of the unsharded call."""
    layout = _KERNEL_LAYOUT.get()
    if layout is None:
        return fn

    def spec(roles):
        return P(*(getattr(layout, r) if r else None for r in roles))

    multi = bool(out_roles) and isinstance(out_roles[0], tuple)
    return shard_map_fn(
        fn, layout.mesh,
        in_specs=tuple(spec(r) for r in in_roles),
        out_specs=tuple(spec(r) for r in out_roles) if multi
        else spec(out_roles),
    )


def serialize_dispatch(mesh: Mesh) -> bool:
    """Whether a mesh needs dispatch throttling at all. XLA:CPU's
    collective rendezvous deadlocks (and then aborts the process) when many
    in-flight partitioned programs oversubscribe the host thread pool —
    seen with >~50 async-queued steps on a 1-core box. Real TPU keeps full
    async pipelining."""
    return all(d.platform == "cpu" for d in mesh.devices.flat)


class DispatchThrottle:
    """Bound the number of in-flight dispatched steps on CPU meshes.

    Full per-step serialization (round 1's workaround) hid the real TPU
    execution mode from every simulated run: nothing ever had more than
    one step in flight, so async multi-step pipelining went untested.
    Instead, keep a window of ``max_in_flight`` un-materialized step
    outputs and block only on the OLDEST once the window fills — the
    simulated mesh now genuinely overlaps dispatch (window > 1) while the
    rendezvous pool stays bounded. On non-CPU meshes this is a no-op.
    """

    def __init__(self, mesh: Mesh, max_in_flight: int = 8):
        self.enabled = serialize_dispatch(mesh)
        self.max_in_flight = max_in_flight
        self._pending: list = []
        self.max_pending_seen = 0  # observability (asserted in tests)

    def after_step(self, out_leaf) -> None:
        """Call with one device value from each dispatched step."""
        if not self.enabled:
            return
        self._pending.append(out_leaf)
        self.max_pending_seen = max(self.max_pending_seen, len(self._pending))
        if len(self._pending) >= self.max_in_flight:
            jax.block_until_ready(self._pending.pop(0))


def make_counting_eval_step(model, mesh: Mesh, in_specs, axes):
    """Jitted sharded eval kernel shared by the parallel engines:
    (params, model_state, x, labels) → (correct, count), psum-ed over
    ``axes``. ``in_specs`` = (param_specs, state_specs, batch_spec,
    batch_spec)."""
    import jax.numpy as jnp
    from jax import lax

    def spmd(params, model_state, x, labels):
        logits, _ = model.apply(params, model_state, x, train=False)
        correct = jnp.sum((jnp.argmax(logits, -1) == labels).astype(jnp.int32))
        return lax.psum(correct, axes), lax.psum(labels.size, axes)

    return jax.jit(
        shard_map_fn(spmd, mesh, in_specs=in_specs, out_specs=(P(), P()))
    )


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def data_sharding(mesh: Mesh, axis_name: str = "data") -> NamedSharding:
    """Leading-axis (batch) sharding over the mesh's data axis."""
    return NamedSharding(mesh, P(axis_name))


def replicate(tree: PyTree, mesh: Mesh) -> PyTree:
    """Place a host pytree replicated on every mesh device.

    The TPU-idiomatic analogue of the reference's one-time rank-0 parameter
    broadcast (``init_parameters``, codes/task2/dist_utils.py:33-37): one
    host copy becomes one replicated device array — no collective needed,
    and all replicas are bitwise identical by construction.
    """
    return jax.device_put(tree, replicated_sharding(mesh))


def shard_batch(batch: PyTree, mesh: Mesh, axis_name: str = "data") -> PyTree:
    """Place a global host batch sharded along its leading dim."""
    return jax.device_put(batch, data_sharding(mesh, axis_name))
