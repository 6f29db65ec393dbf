"""GSPMD model parallelism: parameters sharded over a mesh axis, one
jitted step, XLA inserts the inter-device transfers.

Re-design of the reference's task4 RPC model parallelism (codes/task4/
model.py): there, LeNet is split into SubNetConv/SubNetFC living in other
processes, every forward is two blocking RPC round-trips shipping
activations (model.py:57-60), gradients flow through ``dist_autograd`` and
a ``DistributedOptimizer`` steps parameters where they live via RRefs
(model.py:75-84,126). Here the SAME observable contract — model weights
split across devices, activations moving between them, gradient computation
and optimizer updates happening where each parameter lives — is expressed
as sharding annotations on ONE jitted program: a rule maps each parameter
leaf to a PartitionSpec over the ``stage`` axis, optimizer state inherits
its parameter's spec (the DistributedOptimizer/parameter-server analogue,
also ZeRO-style state sharding), and the XLA SPMD partitioner schedules the
activation collectives on ICI that the reference performed with rpc_sync.

Note on naming: the reference's checklist calls this split "horizontal"
while task4's prose calls the layer split "vertical" (SURVEY.md §2.2). The
GSPMD rule here shards each layer's output features/channels across the
axis — the intra-layer (tensor-parallel flavored) split; the inter-layer
pipelined split is a separate engine (micro-batched pipeline over stacked
stages). Parity is defined by
loss-curve equivalence to single-device training (SURVEY.md §7), which
tests assert for both.

Composable with data parallelism: pass ``batch_axis="data"`` on a 2-D
mesh {"data": D, "stage": S} and the batch shards over ``data`` while
params shard over ``stage`` — GSPMD derives the gradient psum over the
data axis automatically (no explicit collective code).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpudml.capabilities import reject
from tpudml.nn.layers import Module
from tpudml.nn.losses import softmax_cross_entropy
from tpudml.obs.tracer import NULL_SPAN, Tracer
from tpudml.optim import Optimizer
from tpudml.parallel.sharding import DispatchThrottle, kernel_layout
from tpudml.train import (
    TrainState,
    accumulate_grads,
    make_loss_fn,
    resolve_aux_loss_weight,
)

PyTree = Any

RuleFn = Callable[[tuple, jax.ShapeDtypeStruct], P]


def stage_sharding_rules(axis_name: str = "stage") -> RuleFn:
    """Default rule: shard each weight's OUTPUT dimension over the axis.

    kernel[in, out] -> P(None, axis); conv kernel[h, w, in, out] ->
    P(None, None, None, axis); bias[out] -> P(axis). Leaves whose output
    dim does not divide the axis size fall back to replicated at placement
    time (see :func:`apply_rules`).
    """

    def rule(path: tuple, leaf) -> P:
        name = path[-1] if path else ""
        if name == "kernel" and leaf.ndim == 2:
            return P(None, axis_name)
        if name == "kernel" and leaf.ndim == 4:
            return P(None, None, None, axis_name)
        if name == "bias" and leaf.ndim == 1:
            return P(axis_name)
        return P()

    return rule


def replicated_rules() -> RuleFn:
    return lambda path, leaf: P()


def tensor_parallel_rules(axis_name: str = "model") -> RuleFn:
    """Megatron-style intra-layer tensor parallelism for the transformer
    family (beyond reference parity — SURVEY.md §2.3 lists TP as the
    GSPMD-nearly-free stretch row).

    Column-parallel then row-parallel pairs so each block needs one
    all-reduce per sub-layer, which the XLA SPMD partitioner inserts from
    the shardings alone: QKV and MLP-up kernels split on the output
    (head/hidden) dimension, the attention-out and MLP-down kernels split
    on the input dimension; embeddings split on vocab; norms replicated.
    Non-transformer leaves fall back to the generic output-dim rule so the
    rule set still works for mixed models.
    """
    generic = stage_sharding_rules(axis_name)

    def rule(path: tuple, leaf) -> P:
        names = set(path)
        last2 = tuple(path[-2:]) if len(path) >= 2 else ()
        if "attn" in names:
            if last2 and last2[0] in ("q", "k", "v"):
                # Column-parallel: output dim shards head-aligned (the
                # projections are separate kernels, see MultiHeadAttention).
                return P(None, axis_name) if last2[1] == "kernel" else P(axis_name)
            if last2 == ("out", "kernel"):
                return P(axis_name, None)  # row: contracted dim shard
            return P()  # out bias (+ anything else) replicated
        if last2 and last2[0] == "fc1":
            return P(None, axis_name) if last2[1] == "kernel" else P(axis_name)
        if last2 and last2[0] == "fc2":
            return P(axis_name, None) if last2[1] == "kernel" else P()
        if path and path[-1] == "tok_embed":
            return P(axis_name, None)  # vocab shard
        if path and path[-1] == "pos_embed":
            return P()
        if last2 and last2[0] == "head":
            # LM head: column-parallel vocab projection.
            return P(None, axis_name) if last2[1] == "kernel" else P(axis_name)
        if "ln1" in names or "ln2" in names or "ln_f" in names:
            return P()
        return generic(path, leaf)

    return rule


from tpudml.core.pytree import path_names as _path_names  # shared classifier


def apply_rules(rule: RuleFn, params: PyTree, mesh: Mesh) -> PyTree:
    """Per-leaf PartitionSpec tree, demoting specs that don't tile evenly.

    A spec naming mesh axes whose product doesn't divide the corresponding
    leaf dimension is demoted to replicated on that dimension — the
    framework-level guarantee that any model works on any mesh (degenerate
    placements are correct, just less parallel).
    """

    def leaf_spec(key_path, leaf):
        spec = rule(_path_names(key_path), leaf)
        out = []
        for dim, names in enumerate(spec):
            if names is None:
                out.append(None)
                continue
            axis_tuple = names if isinstance(names, tuple) else (names,)
            size = 1
            for a in axis_tuple:
                size *= mesh.shape[a]
            out.append(names if leaf.shape[dim] % size == 0 else None)
        return P(*out)

    return jax.tree_util.tree_map_with_path(leaf_spec, params)


class GSPMDParallel:
    """Model-(+data-)parallel training engine driven by sharding rules.

    Usage::

        mp = GSPMDParallel(model, opt, mesh)           # mesh {"stage": S}
        ts = mp.create_state(key)                      # params sharded
        step = mp.make_train_step()                    # one jitted program

    With a 2-D mesh and ``batch_axis="data"``, DP composes in for free.
    """

    def __init__(
        self,
        model: Module,
        optimizer: Optimizer,
        mesh: Mesh,
        rule: RuleFn | None = None,
        axis_name: str = "stage",
        batch_axis: str | None = None,
        rng_root: jax.Array | None = None,
        accum_steps: int = 1,
        loss: Callable = softmax_cross_entropy,
        aux_loss_weight: float | None = None,
        fused_xent: bool = False,
        save_scores: bool | None = None,
        sentinel: bool | dict = False,
        obs: bool | Tracer = False,
        flash_attn: bool = False,
    ):
        if save_scores and not fused_xent:
            reject("save_scores_needs_fused_xent")
        if fused_xent and (accum_steps != 1 or loss is not softmax_cross_entropy):
            reject("gspmd_fused_xent_accum")
        # flash_attn: run the dense causal trunk on the Pallas flash
        # kernel (same capability row as the DP engine). GSPMD shards
        # batch/heads, never the softmax's sequence axis, but it cannot
        # partition the kernel itself: the step declares its activation
        # layout (_kernel_layout) and the kernel runs per shard.
        self.flash_attn = flash_attn
        if flash_attn:
            import dataclasses

            if getattr(model, "impl", None) != "full" or getattr(
                model, "seq_sharded", False
            ):
                reject("train_flash_attn_dense")
            model = dataclasses.replace(model, impl="flash")
        self.model = model
        self.optimizer = optimizer
        # In-graph step sentinel (tpudml.resilience): under jit/GSPMD the
        # grads the optimizer consumes are logically global arrays —
        # isfinite/norm reductions compile to the right collectives
        # automatically, so the wrapper needs no explicit axis psum.
        self.sentinel = None
        if sentinel:
            from tpudml.resilience.sentinel import attach_sentinel, find_sentinel

            kw = dict(sentinel) if isinstance(sentinel, dict) else {}
            self.optimizer = attach_sentinel(self.optimizer, (), **kw)
            self.sentinel = find_sentinel(self.optimizer)
        self.mesh = mesh
        self.axis_name = axis_name
        if rule is None and axis_name not in mesh.shape:
            raise ValueError(
                f"axis_name {axis_name!r} not in mesh axes {tuple(mesh.shape)}"
            )
        if batch_axis is not None and batch_axis not in mesh.shape:
            raise ValueError(
                f"batch_axis {batch_axis!r} not in mesh axes {tuple(mesh.shape)}"
            )
        self.batch_axis = batch_axis
        self.rule = rule or stage_sharding_rules(axis_name)
        self.rng_root = rng_root
        self.accum_steps = accum_steps
        # Dense-MoE runs get the Switch load-balancing pressure by default
        # (None → α=0.01 when the model contains MoE layers).
        self._loss_fn = make_loss_fn(
            model, loss, resolve_aux_loss_weight(model, aux_loss_weight)
        )
        self.fused_xent = fused_xent
        self.save_scores = save_scores
        self._aux_loss_weight = aux_loss_weight
        self._specs = None  # computed at create_state
        self._throttle = DispatchThrottle(mesh)
        # Observability (tpudml.obs, same knob as the DP engine): one
        # "step" span per dispatch plus the in-graph StepStats pytree in
        # metrics. ``comm_bytes`` stays 0 here — this engine's collectives
        # are inserted by the SPMD partitioner at compile time, so no
        # body-level ring-model price exists (the static analyzer has the
        # same blind spot; see make_train_step's note).
        self.tracer: Tracer | None = None
        self._obs_stats = False
        if obs:
            self.tracer = obs if isinstance(obs, Tracer) else Tracer()
            self._obs_stats = True

    # ---------------------------------------------------------------- state

    def state_specs(self, ts: TrainState) -> TrainState:
        """PartitionSpec tree for the whole TrainState."""
        param_specs = apply_rules(self.rule, ts.params, self.mesh)
        # model_state (e.g. BN stats) follows the same rule; opt state
        # mirrors its parameters (parameter-server semantic, see
        # Optimizer.init_spec).
        state_specs = apply_rules(self.rule, ts.model_state, self.mesh)
        opt_specs = self.optimizer.init_spec(param_specs)
        return TrainState(
            params=param_specs,
            model_state=state_specs,
            opt_state=opt_specs,
            step=P(),
        )

    def _shardings(self, spec_tree):
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s),
            spec_tree,
            is_leaf=lambda x: isinstance(x, P),
        )

    def create_state(self, key: jax.Array) -> TrainState:
        ts = TrainState.create(self.model, self.optimizer, key)
        self._specs = self.state_specs(ts)
        return jax.device_put(ts, self._shardings(self._specs))

    def _kernel_layout(self):
        """The activation layout the placed parameters imply, declared
        while a step is traced so Pallas-backed ops (flash attention, the
        fused add+LN junction) run per shard — the SPMD partitioner
        refuses to partition them. Batch rows split over ``batch_axis``;
        attention heads split over whatever axis the rules put on the
        output columns of an attention ``q`` projection, unless that axis
        is the batch axis doing double duty as FSDP parameter storage."""
        head = None
        for path, spec in jax.tree_util.tree_flatten_with_path(
            self._specs.params, is_leaf=lambda x: isinstance(x, P)
        )[0]:
            if _path_names(path)[-2:] == ("q", "kernel") and len(spec) == 2:
                cols = spec[1]
                if isinstance(cols, str) and cols != self.batch_axis:
                    head = cols
                break
        return kernel_layout(self.mesh, batch=self.batch_axis, head=head)

    # ----------------------------------------------------------------- step

    def make_train_step(self) -> Callable:
        if self._specs is None:
            raise RuntimeError("call create_state() before make_train_step()")
        batch_spec = P(self.batch_axis) if self.batch_axis else P()
        state_shardings = self._shardings(self._specs)
        batch_sharding = NamedSharding(self.mesh, batch_spec)

        fused_loss_fn = None
        if self.fused_xent:
            # Built lazily HERE (not __init__): the sharded loss derives
            # its shard_map region from the head kernel's placed spec,
            # which exists only after create_state ran apply_rules.
            spec_params = self._specs.params
            if not isinstance(spec_params, dict) or "head" not in spec_params:
                raise ValueError(
                    "fused_xent needs a model with a 'head' Dense subtree "
                    "and apply_features (TransformerLM)"
                )
            from tpudml.train import make_lm_fused_sharded_loss_fn

            fused_loss_fn = make_lm_fused_sharded_loss_fn(
                self.model,
                self.mesh,
                kernel_spec=spec_params["head"]["kernel"],
                batch_axis=self.batch_axis,
                save_scores=self.save_scores,
                aux_loss_weight=self._aux_loss_weight,
            )

        def step_impl(ts: TrainState, images, labels):
            rng = None
            if self.rng_root is not None:
                rng = jax.random.fold_in(self.rng_root, ts.step)
            with self._kernel_layout():
                if fused_loss_fn is not None:
                    (loss, model_state), grads = jax.value_and_grad(
                        fused_loss_fn, has_aux=True
                    )(ts.params, ts.model_state, images, labels, rng)
                    metrics = {"loss": loss}
                else:
                    grads, model_state, metrics = accumulate_grads(
                        self._loss_fn, ts.params, ts.model_state, images,
                        labels, rng, self.accum_steps,
                        taint=self.sentinel is not None,
                    )
            new_params, new_opt = self.optimizer.update(grads, ts.opt_state, ts.params)
            if self._obs_stats:
                from tpudml.obs.stepstats import grad_normsq, make_step_stats

                # Grads here are logically global arrays, so this is the
                # exact global grad norm (XLA inserts the reductions).
                metrics = dict(metrics)
                metrics["step_stats"] = make_step_stats(
                    metrics["loss"], grad_normsq(grads), new_opt, 0.0, ts.step
                )
            new_ts = TrainState(
                params=new_params,
                model_state=model_state,
                opt_state=new_opt,
                step=ts.step + 1,
            )
            return new_ts, metrics

        # Donated TrainState (as in the DP engine): params + optimizer state
        # update in place instead of double-buffering — these are the
        # largest live buffers on exactly this engine. Input state is
        # CONSUMED; callers must rebind ts every step.
        jitted = jax.jit(
            step_impl,
            in_shardings=(state_shardings, batch_sharding, batch_sharding),
            out_shardings=(state_shardings, None),
            donate_argnums=(0,),
        )

        def step(ts: TrainState, images, labels):
            images = jax.device_put(jnp.asarray(images), batch_sharding)
            labels = jax.device_put(jnp.asarray(labels), batch_sharding)
            with self._obs_span("train_step"):
                out = jitted(ts, images, labels)
                self._throttle.after_step(out[1]["loss"])
            return out

        # Raw program for tpudml.analysis (wrapper does host-side work).
        # in_specs/mesh_axes seed the dataflow interpreter's top-level
        # states; note GSPMD inserts this engine's collectives at
        # partitioning time, so the static --cost comm volume here only
        # covers explicit shard_map regions (e.g. the fused sharded head).
        step.jitted = jitted
        step.in_specs = (self._specs, batch_spec, batch_spec)
        step.mesh_axes = dict(self.mesh.shape)
        return step

    def _obs_span(self, name: str):
        """Per-dispatch tracer span; a shared no-op object when obs is
        off (the hot path must not allocate per step)."""
        if self.tracer is None:
            return NULL_SPAN
        return self.tracer.span(name, cat="step")

    # ------------------------------------------------------------- evaluate

    def make_eval_step(self) -> Callable:
        if self._specs is None:
            raise RuntimeError("call create_state() before make_eval_step()")
        param_shardings = self._shardings(self._specs.params)
        state_shardings = self._shardings(self._specs.model_state)
        batch_sharding = NamedSharding(
            self.mesh, P(self.batch_axis) if self.batch_axis else P()
        )

        def eval_impl(params, model_state, images, labels):
            with self._kernel_layout():
                logits, _ = self.model.apply(
                    params, model_state, images, train=False)
            return jnp.sum((jnp.argmax(logits, -1) == labels).astype(jnp.int32))

        jitted = jax.jit(
            eval_impl,
            in_shardings=(param_shardings, state_shardings, batch_sharding, batch_sharding),
        )

        def step(params, model_state, images, labels):
            images = jax.device_put(jnp.asarray(images), batch_sharding)
            labels = jax.device_put(jnp.asarray(labels), batch_sharding)
            return jitted(params, model_state, images, labels)

        return step
