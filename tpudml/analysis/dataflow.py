"""Sharding-aware dataflow analysis: a replication-lattice interpreter.

The rule passes before this module were local pattern matchers — they
could spot *a* psum with no bound axis, but not answer the questions
that actually bite on a multi-host slice: "is this value still identical
across the data axis when it reaches the optimizer?", "do all ranks
execute the same collective sequence through this while loop?", "how
many bytes does this step move per collective?". This module answers
them by abstractly interpreting a ``ClosedJaxpr`` and propagating, for
every value and every mesh axis, an element of the replication lattice

    ``replicated``  proven identical across the axis' shards
    ``sharded``     a GLOBAL array dim-partitioned over the axis
                    (outside-shard_map state, seeded from in_specs)
    ``varying``     per-shard bytes may differ (derived from
                    in_specs-split data or ``axis_index`` without an
                    intervening reducing collective)
    ``unknown``     no claim (join of conflicting facts)

through pjit / scan / while / cond / shard_map / custom_vjp sub-jaxprs.
Loop carries reach a fixpoint by iterating the body until states stop
changing (the lattice has height 2, so this converges in a couple of
rounds; ``DataflowResult.iterations`` records the worst loop).

Transfer rules for the collectives that matter:

- ``psum/pmax/pmin/pbroadcast`` over axis *a* → ``replicated`` on *a*
  (every shard computes the same reduction);
- ``all_gather`` over *a* → ``replicated`` (everyone receives all
  shards);
- ``psum_scatter/reduce_scatter/ppermute/all_to_all`` over *a* →
  ``varying`` (each shard keeps a different piece);
- ``axis_index`` over *a* → ``varying`` by definition;
- everything else: ``varying`` is contagious, then ``unknown``, then
  ``sharded``; constants/literals are ``replicated`` everywhere.

On top of the walk this module implements:

- **J112** (missing psum / lost transpose factor): a ``shard_map``
  output whose ``out_specs`` declare it UNSHARDED over a bound axis
  while the body value is ``varying`` over that axis. With
  ``check_vma=False`` (every engine here — custom_vjp regions force it)
  JAX cannot catch this, and each device silently returns different
  bytes for a nominally replicated global — the exact class of bug the
  fused cross-entropy backward had to hand-fix with an out-cotangent
  psum.
- **J113** (unbalanced collective under a shard-dependent loop): a
  ``while`` whose predicate is ``varying`` over axis *a* and whose
  body/cond issue collectives over *a* — shards run different trip
  counts, so some ranks enter a collective their peers never post:
  the slice deadlocks.
- **J115** (allreduce-then-shard): a ``psum`` over *a* whose output is
  consumed ONLY by slices, at least one indexed by ``axis_index`` over
  *a* — each chip keeps 1/N of a fully-replicated reduction, paying
  ~2× the wire bytes a ``psum_scatter`` would (the exact waste ZeRO-1
  removes).

The same walk records every collective's payload/wire bytes and scan
trip counts into ``CommEvent``s — the raw material for the static cost
reports in :mod:`tpudml.analysis.cost`. Everything runs on abstract
values on CPU; no accelerator needed.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any, Iterable

from tpudml.analysis.findings import Finding
from tpudml.comm.timing import collective_wire_bytes

REPLICATED = "replicated"
SHARDED = "sharded"
VARYING = "varying"
UNKNOWN = "unknown"

#: per-value lattice state: axis name -> element; missing = REPLICATED.
AxisState = dict[str, str]

# Collectives that make their result identical across the named axis.
_REPLICATING = frozenset({"psum", "pmax", "pmin", "pbroadcast", "all_gather"})
# Collectives whose result is a per-shard piece.
_VARYING_OUT = frozenset(
    {"psum_scatter", "reduce_scatter", "ppermute", "all_to_all", "pgather"}
)
_COMM = _REPLICATING | _VARYING_OUT


def _repo_rel(path: str) -> str:
    if not path:
        return path
    try:
        rel = os.path.relpath(path, os.getcwd())
    except ValueError:  # pragma: no cover - different drive (windows)
        return path
    return path if rel.startswith("..") else rel


def _src_loc(eqn) -> tuple[str, int]:
    """(file, line) of the user frame that built an equation."""
    from jax._src import source_info_util

    frame = source_info_util.user_frame(eqn.source_info.traceback)
    if frame is None:
        return "", 0
    return _repo_rel(frame.file_name), int(frame.start_line)


def _axis_strs(value: Any) -> tuple[str, ...]:
    if isinstance(value, str):
        return (value,)
    if isinstance(value, (tuple, list, frozenset, set)):
        out: list[str] = []
        for v in value:
            out.extend(_axis_strs(v))
        return tuple(out)
    return ()


def shard_map_dim_axes(specs) -> list[dict[int, tuple[str, ...]]]:
    """Per operand of a ``shard_map`` equation, ``{dim: mesh axes that
    split it}``, read from its ``in_specs`` / ``out_specs`` params
    (PartitionSpecs)."""
    return [
        {d: _axis_strs(entry) for d, entry in enumerate(spec) if entry}
        for spec in specs or ()
    ]


def _eqn_axes(eqn) -> tuple[str, ...]:
    axes: list[str] = []
    for key in ("axes", "axis_name"):
        if key in eqn.params:
            axes.extend(_axis_strs(eqn.params[key]))
    return tuple(axes)


def _inner_jaxpr(obj):
    """Normalize Jaxpr | ClosedJaxpr -> Jaxpr."""
    return obj.jaxpr if hasattr(obj, "jaxpr") else obj


def _is_jaxpr_like(obj) -> bool:
    return hasattr(obj, "eqns") or (
        hasattr(obj, "jaxpr") and hasattr(obj.jaxpr, "eqns")
    )


def _is_var(v) -> bool:
    # Literals carry ``val``; Vars do not.
    return not hasattr(v, "val")


def _aval_bytes(v) -> int:
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        try:
            n *= int(d)
        except (TypeError, ValueError):  # dynamic dim
            pass
    return n * getattr(dtype, "itemsize", 4)


@dataclass
class CommEvent:
    """One collective site in the walked program."""

    kind: str
    axes: tuple[str, ...]
    world: int  # product of the axes' sizes
    payload_bytes: int  # per-shard input bytes at this site
    wire_bytes: float  # ring-model bytes moved per device, per execution
    trips: int  # scan-multiplied executions per step
    file: str = ""
    line: int = 0


@dataclass
class DataflowResult:
    """Everything one interpreter walk produces."""

    findings: list[Finding] = field(default_factory=list)
    comm_events: list[CommEvent] = field(default_factory=list)
    iterations: int = 0  # worst loop-carry fixpoint iteration count
    converged: bool = True
    out_states: list[AxisState] = field(default_factory=list)
    axis_sizes: dict[str, int] = field(default_factory=dict)
    unbounded_loops: int = 0  # while loops (trip count unknown to cost)


# Fixpoint safety valve: the lattice has height 2 so carries settle in
# <= 3 rounds; anything past this is a bug, reported as non-convergence.
_MAX_FIXPOINT_ITERS = 8


class _Interpreter:
    def __init__(self, entrypoint: str, mesh_axes: dict[str, int] | None):
        self.entrypoint = entrypoint
        self.result = DataflowResult(axis_sizes=dict(mesh_axes or {}))
        # id(var) -> AxisState. Var objects are kept alive by the closed
        # jaxpr for the duration of the walk, so ids are stable.
        self.env: dict[int, AxisState] = {}

    # ------------------------------------------------------------- states

    def state(self, v) -> AxisState:
        if not _is_var(v):
            return {}
        return self.env.get(id(v), {})

    def set_state(self, v, st: AxisState) -> None:
        if _is_var(v):
            self.env[id(v)] = {a: e for a, e in st.items() if e != REPLICATED}

    def _join_inputs(self, eqn) -> AxisState:
        out: AxisState = {}
        for v in eqn.invars:
            for a, e in self.state(v).items():
                prev = out.get(a, REPLICATED)
                out[a] = _join(prev, e)
        return out

    # --------------------------------------------------------------- walk

    def interpret(self, obj, trips: int = 1) -> None:
        jaxpr = _inner_jaxpr(obj)
        for cv in getattr(jaxpr, "constvars", ()):
            self.set_state(cv, {})
        producers = {id(ov): e for e in jaxpr.eqns for ov in e.outvars}
        consumers: dict[int, list] = {}
        for e in jaxpr.eqns:
            for v in e.invars:
                if _is_var(v):
                    consumers.setdefault(id(v), []).append(e)
        for eqn in jaxpr.eqns:
            self._eqn(eqn, trips, producers, consumers)
        # J115 runs after the level settles: the slice indices' states
        # (downstream of the psum) only exist once the walk passes them.
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "psum":
                self._check_allreduce_then_slice(eqn, producers, consumers)

    def _eqn(self, eqn, trips, producers, consumers) -> None:
        name = eqn.primitive.name
        if name in _COMM:
            self._comm(eqn, trips)
            return
        if name == "axis_index":
            st: AxisState = {a: VARYING for a in _eqn_axes(eqn)}
            for ov in eqn.outvars:
                self.set_state(ov, st)
            return
        if name == "shard_map":
            self._shard_map(eqn, trips)
            return
        if name == "scan":
            self._scan(eqn, trips)
            return
        if name == "while":
            self._while(eqn, trips)
            return
        if name == "cond":
            self._cond(eqn, trips)
            return
        sub = self._call_jaxpr(eqn)
        if sub is not None:
            self._call(eqn, sub, trips)
            return
        # Default transfer: varying is contagious, then unknown/sharded.
        joined = self._join_inputs(eqn)
        for ov in eqn.outvars:
            self.set_state(ov, joined)

    # --------------------------------------------------------- collectives

    def _comm(self, eqn, trips: int) -> None:
        axes = _eqn_axes(eqn)
        name = eqn.primitive.name
        joined = self._join_inputs(eqn)
        groups = eqn.params.get("axis_index_groups")
        out = dict(joined)
        for a in axes:
            if groups:
                # Partial-group collectives reduce within subgroups only;
                # claim nothing rather than risk a false J112.
                out[a] = UNKNOWN
            elif name in _REPLICATING:
                out[a] = REPLICATED
            else:
                out[a] = VARYING
        for ov in eqn.outvars:
            self.set_state(ov, out)
        world = 1
        for a in axes:
            world *= self.result.axis_sizes.get(a, 1)
        if world <= 1:
            return
        payload = sum(_aval_bytes(v) for v in eqn.invars if _is_var(v))
        wire = collective_wire_bytes(name, payload, world)
        f, ln = _src_loc(eqn)
        self.result.comm_events.append(CommEvent(
            kind=name, axes=tuple(sorted(axes)), world=world,
            payload_bytes=payload, wire_bytes=wire, trips=trips,
            file=f, line=ln,
        ))

    def _check_allreduce_then_slice(self, eqn, producers, consumers) -> None:
        """J115 at the psum site: every consumer of the allreduced value
        is a slice, and at least one is a dynamic_slice whose start index
        varies over the psum's own axis (the ``axis_index``-addressed
        keep-my-1/N pattern a psum_scatter serves at half the wire
        bytes)."""
        axes = set(_eqn_axes(eqn))
        if not axes:
            return
        for ov in eqn.outvars:
            uses = consumers.get(id(ov), [])
            if not uses:
                continue
            if any(u.primitive.name not in ("slice", "dynamic_slice",
                                            "convert_element_type")
                   for u in uses):
                continue
            hit = None
            for u in uses:
                if u.primitive.name != "dynamic_slice":
                    continue
                idx_axes = set()
                for iv in u.invars[1:]:
                    idx_axes.update(
                        a for a, e in self.state(iv).items() if e == VARYING
                    )
                if idx_axes & axes:
                    hit = u
                    break
            if hit is None:
                continue
            world = 1
            for a in sorted(axes):
                world *= self.result.axis_sizes.get(a, 2)
            f, ln = _src_loc(hit)
            self.result.findings.append(Finding(
                "J115",
                f"psum (allreduce) over axis {sorted(axes)} whose result "
                f"is consumed only by per-shard slices (dynamic_slice "
                f"indexed by axis_index) — every chip receives the full "
                f"reduction and keeps 1/{world}; a psum_scatter moves "
                f"about half the bytes and lands each shard where it is "
                f"used",
                file=f, line=ln, entrypoint=self.entrypoint,
            ))

    # ----------------------------------------------------------- shard_map

    def _shard_map(self, eqn, trips: int) -> None:
        mesh = eqn.params.get("mesh")
        body = eqn.params.get("jaxpr")
        in_names = shard_map_dim_axes(eqn.params.get("in_specs"))
        out_names = shard_map_dim_axes(eqn.params.get("out_specs"))
        if mesh is None or body is None:
            return
        try:
            mesh_axes = {str(a): int(s)
                         for a, s in zip(mesh.axis_names, mesh.devices.shape)}
        except Exception:
            mesh_axes = {str(a): int(mesh.shape[a]) for a in mesh.axis_names}
        self.result.axis_sizes.update(mesh_axes)
        jaxpr = _inner_jaxpr(body)
        # Body invar states are fully determined by in_specs: axes the
        # specs split a dim over differ per shard; the rest of the bound
        # axes see identical bytes of the one global value. Axes bound
        # further out (nested shard_map) propagate from the outer state.
        for var, names in zip(jaxpr.invars, in_names):
            st: AxisState = {}
            split_axes = set()
            for dim_axes in names.values():
                split_axes.update(dim_axes)
            for a in mesh_axes:
                st[a] = VARYING if a in split_axes else REPLICATED
            self.set_state(var, st)
        # Outer axes not bound by this mesh: carry through from inputs.
        outer_axes = {
            a for v in eqn.invars for a in self.state(v) if a not in mesh_axes
        }
        if outer_axes:
            for var, src in zip(jaxpr.invars, eqn.invars):
                st = dict(self.state(var))
                for a in outer_axes:
                    e = self.state(src).get(a, REPLICATED)
                    if e != REPLICATED:
                        st[a] = UNKNOWN
                self.set_state(var, st)
        self.interpret(body, trips)
        check_vma = bool(eqn.params.get("check_vma", False))
        for ov, body_ov, names in zip(eqn.outvars, jaxpr.outvars, out_names):
            declared = set()
            for dim_axes in names.values():
                declared.update(dim_axes)
            body_st = self.state(body_ov)
            out_st: AxisState = {}
            for a in mesh_axes:
                if a in declared:
                    out_st[a] = SHARDED
                elif body_st.get(a, REPLICATED) == VARYING:
                    if not check_vma:
                        prod_eqn = self._producer_of(jaxpr, body_ov)
                        f, ln = (_src_loc(prod_eqn) if prod_eqn is not None
                                 else _src_loc(eqn))
                        self.result.findings.append(Finding(
                            "J112",
                            f"shard_map output is declared UNSHARDED over "
                            f"mesh axis '{a}' but the body value varies "
                            f"per shard — no reducing collective (psum/"
                            f"all_gather) stands between the shard-local "
                            f"computation and the replicated output; with "
                            f"check_vma=False each device silently returns "
                            f"different bytes (the missing-psum / lost "
                            f"transpose-factor class)",
                            file=f, line=ln, entrypoint=self.entrypoint,
                        ))
                    out_st[a] = UNKNOWN
                elif body_st.get(a, REPLICATED) == UNKNOWN:
                    out_st[a] = UNKNOWN
            # Outer axes carry through.
            for a, e in body_st.items():
                if a not in mesh_axes and e != REPLICATED:
                    out_st[a] = e
            self.set_state(ov, out_st)

    @staticmethod
    def _producer_of(jaxpr, var):
        for e in jaxpr.eqns:
            if any(ov is var for ov in e.outvars):
                return e
        return None

    # -------------------------------------------------------- control flow

    def _scan(self, eqn, trips: int) -> None:
        body = eqn.params["jaxpr"]
        jaxpr = _inner_jaxpr(body)
        n_consts = int(eqn.params.get("num_consts", 0))
        n_carry = int(eqn.params.get("num_carry", 0))
        length = int(eqn.params.get("length", 1) or 1)
        self._loop_fixpoint(
            jaxpr,
            eqn.invars,
            n_consts=n_consts,
            n_carry=n_carry,
            carry_out_slice=slice(0, n_carry),
            trips=trips * max(length, 1),
        )
        # Outputs: carries then stacked ys, straight from body out states.
        for ov, body_ov in zip(eqn.outvars, jaxpr.outvars):
            self.set_state(ov, dict(self.state(body_ov)))

    def _while(self, eqn, trips: int) -> None:
        cond_jaxpr = eqn.params["cond_jaxpr"]
        body_jaxpr = eqn.params["body_jaxpr"]
        cn = int(eqn.params.get("cond_nconsts", 0))
        bn = int(eqn.params.get("body_nconsts", 0))
        cond = _inner_jaxpr(cond_jaxpr)
        body = _inner_jaxpr(body_jaxpr)
        carry_in = eqn.invars[cn + bn:]
        # Fixpoint on the body carry.
        self._loop_fixpoint(
            body,
            list(eqn.invars[cn:cn + bn]) + list(carry_in),
            n_consts=bn,
            n_carry=len(carry_in),
            carry_out_slice=slice(0, len(carry_in)),
            trips=trips,
        )
        self.result.unbounded_loops += 1
        # Evaluate the predicate on the settled carry states.
        for var, src in zip(cond.invars[:cn], eqn.invars[:cn]):
            self.set_state(var, dict(self.state(src)))
        for var, body_ov in zip(cond.invars[cn:], body.outvars):
            self.set_state(var, dict(self.state(body_ov)))
        self.interpret(cond_jaxpr, trips)
        pred_st = self.state(cond.outvars[0]) if cond.outvars else {}
        varying_axes = {a for a, e in pred_st.items() if e == VARYING}
        if varying_axes:
            comm_axes = set()
            for sub in (cond, body):
                comm_axes |= _comm_axes(sub)
            clash = sorted(varying_axes & comm_axes)
            if clash:
                f, ln = _src_loc(eqn)
                self.result.findings.append(Finding(
                    "J113",
                    f"while loop's predicate varies per shard over axis "
                    f"{clash} and its body/cond issue collectives over the "
                    f"same axis — shards run different trip counts, so "
                    f"some ranks post a collective their peers never "
                    f"enter: the slice deadlocks; derive the predicate "
                    f"from a reduced (psum/pmax) value so every shard "
                    f"agrees on the trip count",
                    file=f, line=ln, entrypoint=self.entrypoint,
                ))
        for ov, body_ov in zip(eqn.outvars, body.outvars):
            self.set_state(ov, dict(self.state(body_ov)))

    def _loop_fixpoint(self, body_jaxpr, invars, *, n_consts: int,
                       n_carry: int, carry_out_slice: slice,
                       trips: int) -> None:
        """Interpret a loop body until the carry states stop changing."""
        for var, src in zip(body_jaxpr.invars[:n_consts], invars[:n_consts]):
            self.set_state(var, dict(self.state(src)))
        carry_vars = body_jaxpr.invars[n_consts:n_consts + n_carry]
        xs_vars = body_jaxpr.invars[n_consts + n_carry:]
        for var, src in zip(carry_vars, invars[n_consts:n_consts + n_carry]):
            self.set_state(var, dict(self.state(src)))
        for var, src in zip(xs_vars, invars[n_consts + n_carry:]):
            self.set_state(var, dict(self.state(src)))
        events_mark = len(self.result.comm_events)
        findings_mark = len(self.result.findings)
        for it in range(1, _MAX_FIXPOINT_ITERS + 1):
            # Re-walks emit duplicate comm events/findings; keep only the
            # final iteration's.
            del self.result.comm_events[events_mark:]
            del self.result.findings[findings_mark:]
            self.interpret(body_jaxpr, trips)
            changed = False
            outs = body_jaxpr.outvars[carry_out_slice]
            for var, out in zip(carry_vars, outs):
                joined = dict(self.state(var))
                for a, e in self.state(out).items():
                    new = _join(joined.get(a, REPLICATED), e)
                    if new != joined.get(a, REPLICATED):
                        joined[a] = new
                        changed = True
                if changed:
                    self.set_state(var, joined)
            self.result.iterations = max(self.result.iterations, it)
            if not changed:
                return
        self.result.converged = False

    def _cond(self, eqn, trips: int) -> None:
        branches = eqn.params.get("branches", ())
        operands = eqn.invars[1:]
        out_states: list[AxisState] = [dict() for _ in eqn.outvars]
        for br in branches:
            jaxpr = _inner_jaxpr(br)
            for var, src in zip(jaxpr.invars, operands):
                self.set_state(var, dict(self.state(src)))
            self.interpret(br, trips)
            for i, body_ov in enumerate(jaxpr.outvars):
                for a, e in self.state(body_ov).items():
                    prev = out_states[i].get(a, REPLICATED)
                    out_states[i][a] = _join(prev, e)
        # A varying predicate makes the branch choice itself per-shard.
        pred_st = self.state(eqn.invars[0])
        pred_var = {a for a, e in pred_st.items() if e == VARYING}
        for ov, st in zip(eqn.outvars, out_states):
            st = dict(st)
            for a in pred_var:
                st[a] = _join(st.get(a, REPLICATED), UNKNOWN)
            self.set_state(ov, st)

    # -------------------------------------------------------------- calls

    @staticmethod
    def _call_jaxpr(eqn):
        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
            sub = eqn.params.get(key)
            if sub is not None and _is_jaxpr_like(sub):
                return sub
        return None

    def _call(self, eqn, sub, trips: int) -> None:
        jaxpr = _inner_jaxpr(sub)
        n_body, n_eqn = len(jaxpr.invars), len(eqn.invars)
        if n_body == n_eqn:
            pairs = zip(jaxpr.invars, eqn.invars)
        elif n_body < n_eqn:
            # Consts-first conventions (custom_vjp num_consts): the
            # trailing eqn invars are the real arguments.
            pairs = zip(jaxpr.invars, eqn.invars[n_eqn - n_body:])
        else:
            joined = self._join_inputs(eqn)
            pairs = ((v, None) for v in jaxpr.invars)
            for v, _ in pairs:
                self.set_state(v, dict(joined))
            pairs = ()
        for var, src in pairs:
            self.set_state(var, dict(self.state(src)))
        self.interpret(sub, trips)
        if len(jaxpr.outvars) == len(eqn.outvars):
            for ov, body_ov in zip(eqn.outvars, jaxpr.outvars):
                self.set_state(ov, dict(self.state(body_ov)))
        else:
            joined: AxisState = {}
            for body_ov in jaxpr.outvars:
                for a, e in self.state(body_ov).items():
                    joined[a] = _join(joined.get(a, REPLICATED), e)
            for ov in eqn.outvars:
                self.set_state(ov, dict(joined))


def _join(a: str, b: str) -> str:
    if a == b:
        return a
    if VARYING in (a, b):
        return VARYING
    return UNKNOWN


def _comm_axes(obj) -> set[str]:
    """All axes any communicating collective touches, recursively."""
    jaxpr = _inner_jaxpr(obj)
    axes: set[str] = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in _COMM:
            axes.update(_eqn_axes(eqn))
        for val in eqn.params.values():
            if _is_jaxpr_like(val):
                axes |= _comm_axes(val)
            elif isinstance(val, (tuple, list)):
                for item in val:
                    if _is_jaxpr_like(item):
                        axes |= _comm_axes(item)
    return axes


def _seed_states(
    jaxpr, in_specs, mesh_axes: dict[str, int] | None
) -> Iterable[tuple[Any, AxisState]]:
    """Top-level invar states from entrypoint in_specs: an axis a spec
    mentions partitions that argument (``sharded``); the rest of the
    mesh is ``replicated`` (the engines place state either replicated or
    explicitly sharded — there is no third placement)."""
    if in_specs is None:
        return [(v, {}) for v in jaxpr.invars]
    import jax

    flat_specs: list = []
    try:
        for spec in in_specs:
            leaves = jax.tree.leaves(
                spec, is_leaf=lambda x: x is None or _is_partition_spec(x)
            )
            flat_specs.extend(leaves if leaves else [None])
    except Exception:
        flat_specs = []
    out = []
    for i, v in enumerate(jaxpr.invars):
        spec = flat_specs[i] if i < len(flat_specs) else None
        st: AxisState = {}
        if _is_partition_spec(spec):
            for a in _axis_strs(tuple(spec)):
                st[a] = SHARDED
        out.append((v, st))
    return out


def _is_partition_spec(x) -> bool:
    return type(x).__name__ == "PartitionSpec"


def analyze_dataflow(
    closed,
    entrypoint: str = "",
    in_specs=None,
    mesh_axes: dict[str, int] | None = None,
) -> DataflowResult:
    """Run the replication-lattice interpreter over one traced program.

    ``in_specs`` is the entrypoint's (optional) argument PartitionSpec
    pytree — flattened against the top-level invars to seed ``sharded``
    states; ``mesh_axes`` maps axis name -> size for collectives outside
    any shard_map (sizes inside shard_map come from the mesh param).
    """
    interp = _Interpreter(entrypoint, mesh_axes)
    jaxpr = _inner_jaxpr(closed)
    for v, st in _seed_states(jaxpr, in_specs, mesh_axes):
        interp.set_state(v, st)
    try:
        interp.interpret(closed)
    except RecursionError:
        interp.result.converged = False
        interp.result.findings.append(Finding(
            "J100",
            "dataflow interpreter exceeded recursion depth (jaxpr nesting)",
            entrypoint=entrypoint,
        ))
    interp.result.out_states = [
        dict(interp.state(v)) for v in jaxpr.outvars
    ]
    if not interp.result.converged and not any(
        f.rule == "J100" for f in interp.result.findings
    ):
        interp.result.findings.append(Finding(
            "J100",
            f"dataflow fixpoint did not converge within "
            f"{_MAX_FIXPOINT_ITERS} iterations",
            entrypoint=entrypoint,
        ))
    return interp.result
