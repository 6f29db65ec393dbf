"""Pass 1 — jaxpr analysis of traced train steps.

Walks a ``ClosedJaxpr`` (recursing through pjit/scan/while/cond/shard_map
sub-jaxprs while tracking which collective axis names are bound) and
flags the hazard classes that otherwise fail only at runtime on a
multi-host slice:

- J101  collectives whose axis name is not bound by an enclosing
        shard_map/pmap (the same class of bug also surfaces as a trace
        NameError — ``analyze_callable`` converts that to J101 too);
- J102  cond/switch branches that issue different collective sequences —
        with a shard-dependent predicate this deadlocks the slice;
- J103  host callback primitives inside the step (debug prints,
        pure/io_callback): every call is a device→host sync;
- J104  bf16→f32 upcast edges whose results feed non-accumulating
        consumers (mixed-precision leaks that silently re-inflate
        bandwidth); explicit accumulation (reductions, dots) is exempt;
- J105  large (>1 MiB) arrays captured as jaxpr constants — baked into
        the program instead of passed (and donated) as arguments;
- J106  (from the lowered module, not the jaxpr) steps whose large
        inputs carry no donation aliasing at all;
- J107  the UNSHARDED fused cross-entropy head consuming a kernel whose
        vocab (last) dimension is sharded over a mesh axis — each shard
        then normalizes over only its local vocab slice and the losses
        are silently wrong; the sharded wrapper
        (``sharded_linear_cross_entropy``) merges per-shard statistics
        and stays silent.
- J108  a REPLICATED optimizer update under ``shard_map`` on a mesh with
        a data axis: gradient-shaped tensors are allreduced (psum) over
        the axis and returned replicated, with no reduce-scatter in
        sight — every chip pays the full optimizer FLOPs/HBM, the exact
        waste ZeRO-1 weight-update sharding (``optim.zero1``) removes.
- J109  ``lax.ragged_dot``'s stock grouped-transpose dW surviving into a
        backward: a ``ragged_dot_general`` that contracts its ragged
        dim, which the generic lowering rolls out as ``[E, P, ·]``
        range-masked operands — E× the dense dW FLOPs (the 3.4×
        ragged-MoE backward of BASELINE round 5); the grouped-dW kernel
        path (``ops.moe_kernel``) never emits it and stays silent.
- J110  a decode-marked program (``tpudml.serve``'s jitted per-token
        step) that recomputes FULL-sequence attention per emitted token:
        a softmax ``exp`` over scores whose trailing two (query, key)
        dims are both > 1 means the step pays O(T²) attention for one
        token — generation goes quadratic-per-token instead of reading
        the KV cache. The cache-carrying step's scores are [B, H, 1, L]
        (query dim 1) and stay silent.
- J111  a training step that UPDATES parameters (≥2 elementwise ``sub``
        equations whose minuend is a jaxpr invar, possibly through
        reshape/concat/slice — the SGD/Adam ``p - update`` shape, incl.
        ZeRO-1's flattened chunks) while the WHOLE program contains no
        ``is_finite`` predicate: one non-finite microbatch then reaches
        the weights and, under synchronous collectives, every replica at
        once — the unrecoverable-divergence mode the step sentinel
        (``resilience.GradSentinel``) closes. Sentinel-wrapped steps
        carry the finiteness check in-graph and stay silent.

- J114  a buffer donated to a jitted call (``donated_invars``) consumed
        AGAIN afterwards — by a later equation at the same level, by the
        program's own outputs, or twice within the one call: XLA may
        have aliased the memory to an output, so the second read sees
        whatever the donating program wrote over it.

Since the replication-lattice interpreter landed
(:mod:`tpudml.analysis.dataflow`), ``analyze_closed_jaxpr`` also runs
the sharding-aware dataflow rules over the same traced program: J112
(missing psum under ``check_vma=False``), J113 (shard-dependent while
trip counts around collectives), J115 (allreduce-then-shard), and —
when an HBM budget is supplied — J116 from the static cost walk
(:mod:`tpudml.analysis.cost`).

The pass is backend-free: everything works on abstract values on CPU.
"""

from __future__ import annotations

import re
from typing import Any, Callable, Iterable

from tpudml.analysis.dataflow import _axis_strs, _src_loc, shard_map_dim_axes
from tpudml.analysis.findings import Finding

# Primitives that require a bound axis name (J101). The subset that
# actually communicates (everything but axis_index) forms the J102
# branch signature.
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "ppermute", "pbroadcast", "all_gather",
    "all_to_all", "reduce_scatter", "psum_scatter", "pgather", "axis_index",
})
COMM_PRIMS = COLLECTIVE_PRIMS - {"axis_index"}

CALLBACK_PRIMS = frozenset({
    "debug_callback", "debug_print", "pure_callback", "io_callback", "callback",
    "outside_call", "host_callback_call", "infeed", "outfeed",
})

# Direct consumers under which a bf16→f32 upcast is the intended
# accumulate-in-f32 idiom (J104 stays silent).
ACCUM_OK_PRIMS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_precision", "dot_general", "conv_general_dilated",
    "cumsum", "cumprod", "cumlogsumexp", "cummax", "cummin",
    "scan", "while", "psum", "psum_scatter", "reduce_scatter",
    "convert_element_type",
})

LARGE_CONST_BYTES = 1 << 20  # 1 MiB

# The fused cross-entropy dispatchers are jitted under marker names that
# survive as pjit ``name`` params in any traced jaxpr (J107). Mirrors
# FUSED_XENT_MARKER / SHARDED_XENT_MARKER in tpudml/ops/xent_kernel.py —
# string literals here so the analyzer never imports kernel code; the
# pairing is pinned by test_analysis.
FUSED_XENT_NAME = "_fused_xent_unsharded"
SHARDED_XENT_NAME = "_fused_xent_sharded"

# The serving decode step is jitted under this marker name (J110).
# Mirrors SERVE_DECODE_MARKER in tpudml/serve/engine.py — a string
# literal for the same reason; the pairing is pinned by test_analysis.
SERVE_DECODE_NAME = "_serve_decode_step"

# Paged/speculative decode steps carry their own marker names (J117) —
# NOT the dense marker: the spec verify window's [B, H, K+1, L] softmax
# would false-fire J110's both-trailing-dims>1 check on a single-token
# contract. Mirror PAGED_DECODE_MARKER (tpudml/serve/paged.py) and
# SPEC_DECODE_MARKER (tpudml/serve/spec.py); pinned by test_analysis.
PAGED_DECODE_NAMES = ("_serve_paged_decode_step", "_serve_spec_decode_step")

# The fused decode-tail dispatchers (head matmul + greedy pick + step
# stats as one vocab-tiled program) are jitted under these marker names
# (J119's tail check skips their bodies — their internal argmax IS the
# fused pick). Mirror FUSED_HEAD_MARKER / FUSED_HEAD_INT8_MARKER in
# tpudml/ops/decode_head.py; pinned by test_analysis.
FUSED_HEAD_NAMES = ("_fused_decode_head", "_fused_decode_head_int8")

# The chunked psum-overlapped TP matmul is jitted under this marker name
# (J119's overlap-claim check). Mirrors TP_OVERLAP_MARKER in
# tpudml/parallel/overlap.py; pinned by test_analysis.
TP_OVERLAP_NAME = "_tp_overlap_matmul"

# Decode-marked pjit names whose bodies J119's unfused-tail check scans.
_DECODE_TAIL_NAMES = (SERVE_DECODE_NAME,) + PAGED_DECODE_NAMES

# Primitives a last-dim sharding survives on the way from a shard_map
# body invar to the fused head's w operand (J107 taint propagation).
_LASTDIM_PRESERVING = frozenset({"convert_element_type", "copy"})

# Mesh axis names that conventionally carry data parallelism (J108 only
# reasons about replicated WEIGHT updates, which live on these axes).
_DATA_AXIS_NAMES = frozenset({"data", "batch"})

# Primitives through which "this value is (a repartitioned view of) a
# jaxpr invar" survives on the way to a parameter-update ``sub`` (J111
# taint) — ZeRO-1 reshapes/concatenates/slices param leaves into flat
# chunks before its inner update subtracts from them. Compute primitives
# (dot, conv, reductions) deliberately KILL the taint: activations
# derived from the batch never count as parameters.
_J111_PRESERVING = frozenset({
    "reshape", "concatenate", "slice", "dynamic_slice",
    "convert_element_type", "transpose", "squeeze", "copy",
})


def _eqn_axes(eqn) -> tuple[str, ...]:
    axes: list[str] = []
    for key in ("axes", "axis_name"):
        if key in eqn.params:
            axes.extend(_axis_strs(eqn.params[key]))
    return tuple(axes)


def _inner_jaxpr(obj):
    """Normalize Jaxpr | ClosedJaxpr -> (Jaxpr, consts)."""
    if hasattr(obj, "jaxpr"):  # ClosedJaxpr
        return obj.jaxpr, getattr(obj, "consts", ())
    return obj, ()


def _is_jaxpr_like(obj) -> bool:
    return hasattr(obj, "eqns") or (
        hasattr(obj, "jaxpr") and hasattr(obj.jaxpr, "eqns")
    )


def _sub_jaxprs(eqn) -> Iterable[tuple[Any, frozenset[str]]]:
    """(sub-jaxpr, extra bound axes) pairs under an equation."""
    extra: frozenset[str] = frozenset()
    name = eqn.primitive.name
    if name == "shard_map":
        mesh = eqn.params.get("mesh")
        if mesh is not None:
            extra = frozenset(str(a) for a in mesh.axis_names)
    elif name in ("xla_pmap", "pmap"):
        extra = frozenset(_axis_strs(eqn.params.get("axis_name", ())))
    for val in eqn.params.values():
        if _is_jaxpr_like(val):
            yield val, extra
        elif isinstance(val, (tuple, list)):
            for item in val:
                if _is_jaxpr_like(item):
                    yield item, extra


def _collective_signature(obj) -> tuple[tuple[str, tuple[str, ...]], ...]:
    """Ordered (prim, axes) sequence of communicating collectives inside a
    jaxpr, recursing through sub-jaxprs — the J102 branch fingerprint."""
    jaxpr, _ = _inner_jaxpr(obj)
    sig: list[tuple[str, tuple[str, ...]]] = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in COMM_PRIMS:
            sig.append((eqn.primitive.name, tuple(sorted(_eqn_axes(eqn)))))
        for sub, _extra in _sub_jaxprs(eqn):
            sig.extend(_collective_signature(sub))
    return tuple(sig)


def collective_shape_signature(obj) -> tuple:
    """Ordered ``(prim, axes, operand shape)`` sequence of communicating
    collectives, recursing through sub-jaxprs — the shape-carrying
    variant of the J102 fingerprint that the protocol pass's P302 check
    (``analysis/protocol.py``) compares across the ranks of one MPMD
    stage group."""
    jaxpr, _ = _inner_jaxpr(obj)
    sig: list = []
    for eqn in jaxpr.eqns:
        # Under shard_map(check_vma=True) psum/all_gather bind as
        # ``*_invariant`` variants of the same wire collective; normalize
        # so signatures compare across ranks traced either way.
        name = eqn.primitive.name.removesuffix("_invariant")
        if name in COMM_PRIMS:
            shape = ()
            if eqn.invars:
                shape = tuple(getattr(eqn.invars[0].aval, "shape", ()))
            sig.append((
                name,
                tuple(sorted(_eqn_axes(eqn))),
                shape,
            ))
        for sub, _extra in _sub_jaxprs(eqn):
            sig.extend(collective_shape_signature(sub))
    return tuple(sig)


def _check_upcasts(jaxpr, entrypoint: str, findings: list[Finding]) -> None:
    """J104 within one jaxpr level: convert_element_type bf16→f32 whose
    result has a non-accumulating direct consumer."""
    import numpy as np

    consumers: dict[int, list[str]] = {}
    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            if hasattr(v, "count") or type(v).__name__ == "Var":
                consumers.setdefault(id(v), []).append(eqn.primitive.name)
    for eqn in jaxpr.eqns:
        if eqn.primitive.name != "convert_element_type":
            continue
        try:
            src_dtype = eqn.invars[0].aval.dtype
            dst_dtype = np.dtype(eqn.params["new_dtype"])
        except Exception:
            continue
        if str(src_dtype) != "bfloat16" or str(dst_dtype) != "float32":
            continue
        used_by = consumers.get(id(eqn.outvars[0]), [])
        bad = [p for p in used_by if p not in ACCUM_OK_PRIMS]
        if bad:
            f, ln = _src_loc(eqn)
            findings.append(Finding(
                "J104",
                f"bf16 value upcast to f32 feeds non-accumulating "
                f"consumer(s) {sorted(set(bad))}",
                file=f, line=ln, entrypoint=entrypoint,
            ))


def _check_ragged_transpose(jaxpr, entrypoint: str,
                            findings: list[Finding]) -> None:
    """J109 within one jaxpr level: ``lax.ragged_dot``'s transpose rule
    left in a backward. The stock VJP's dW is a ``ragged_dot_general``
    whose RAGGED dimension is the one it contracts (``[P, K] × [P, N] →
    [E, K, N]``): JAX's generic lowering rolls it out as E range-masked
    copies of both operands — E× the dense dW FLOPs plus an E-fold
    activation materialization — and the chip's own instruction for it
    has not been measured against the grouped-dW path (ops.moe_kernel),
    which never emits the primitive and so stays silent."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name != "ragged_dot_general":
            continue
        dims = eqn.params["ragged_dot_dimension_numbers"]
        (lhs_contract, _), _ = dims.dot_dimension_numbers
        if not set(dims.lhs_ragged_dimensions) <= set(lhs_contract):
            continue
        f, ln = _src_loc(eqn)
        e_dim = eqn.outvars[0].aval.shape[0]
        findings.append(Finding(
            "J109",
            f"ragged_dot grouped-transpose dW: ragged_dot_general "
            f"contracting its ragged dim into [{e_dim}, ·, ·] — {e_dim}× the "
            f"dense dW FLOPs in the backward where it is rolled out as "
            f"range masks",
            file=f, line=ln, entrypoint=entrypoint,
        ))


def _fused_xent_seed(eqn) -> dict[int, tuple[str, ...]]:
    """J107 taint seed for one shard_map equation: body invars whose
    LAST dimension the in_specs shard, mapped to the sharding axes."""
    in_names = shard_map_dim_axes(eqn.params.get("in_specs"))
    body = eqn.params.get("jaxpr")
    if body is None:
        return {}
    jaxpr, _ = _inner_jaxpr(body)
    tainted: dict[int, tuple[str, ...]] = {}
    for var, names in zip(jaxpr.invars, in_names):
        ndim = getattr(getattr(var, "aval", None), "ndim", 0)
        axes = names.get(ndim - 1, ()) if ndim else ()
        if axes:
            tainted[id(var)] = axes
    return tainted


def _check_fused_xent(obj, tainted: dict[int, tuple[str, ...]],
                      entrypoint: str, findings: list[Finding]) -> None:
    """J107 within a shard_map body: propagate 'vocab dim is sharded'
    from the seed through last-dim-preserving ops (and all_gathers over
    other dims) to the w operand (position 1) of a pjit carrying the
    unsharded fused-xent marker name. The sharded dispatcher's distinct
    marker keeps correct compositions silent."""
    jaxpr, _ = _inner_jaxpr(obj)
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "jit":
            jit_name = str(eqn.params.get("name", ""))
            if jit_name == FUSED_XENT_NAME:
                axes = (tainted.get(id(eqn.invars[1]))
                        if len(eqn.invars) > 1 else None)
                if axes:
                    f, ln = _src_loc(eqn)
                    findings.append(Finding(
                        "J107",
                        f"fused cross-entropy head consumes a kernel whose "
                        f"vocab (last) dim is sharded over mesh axis "
                        f"{list(axes)} without the shard-merge wrapper — "
                        f"each shard normalizes over its local slice only; "
                        f"use sharded_linear_cross_entropy(axis_name=...)",
                        file=f, line=ln, entrypoint=entrypoint,
                    ))
                continue
            if jit_name == SHARDED_XENT_NAME:
                continue  # merge wrapper present — correct by construction
            sub = eqn.params.get("jaxpr")
            if sub is not None:
                sj, _ = _inner_jaxpr(sub)
                inner = {
                    id(sj.invars[i]): axes
                    for i, v in enumerate(eqn.invars)
                    if (axes := tainted.get(id(v))) and i < len(sj.invars)
                }
                if inner:
                    _check_fused_xent(sub, inner, entrypoint, findings)
            continue
        if not eqn.invars or not eqn.outvars:
            continue
        axes = tainted.get(id(eqn.invars[0]))
        if not axes:
            continue
        if name in _LASTDIM_PRESERVING:
            tainted[id(eqn.outvars[0])] = axes
        elif name == "all_gather":
            out = eqn.outvars[0]
            ndim = getattr(getattr(out, "aval", None), "ndim", 0)
            if eqn.params.get("all_gather_dimension", 0) != ndim - 1:
                tainted[id(out)] = axes


def _find_wide_softmax_exp(obj):
    """First ``exp`` equation (recursing through sub-jaxprs) whose operand
    keeps BOTH trailing dims > 1 — the [.., T, T] attention-probability
    tensor of a full-sequence softmax. A cache-reading decode step's
    softmax runs on [B, H, 1, L] scores (one query row per emitted
    token), so its exp never matches. Fused-head marker bodies are
    skipped: their lse statistics exp over [B, V_tile] vocab columns,
    not attention scores."""
    jaxpr, _ = _inner_jaxpr(obj)
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "jit"
                and str(eqn.params.get("name", "")) in FUSED_HEAD_NAMES):
            continue
        if eqn.primitive.name == "exp":
            shape = tuple(
                getattr(getattr(eqn.invars[0], "aval", None), "shape", ())
            )
            if len(shape) >= 2 and shape[-1] > 1 and shape[-2] > 1:
                return eqn, shape
        for sub, _extra in _sub_jaxprs(eqn):
            hit = _find_wide_softmax_exp(sub)
            if hit is not None:
                return hit
    return None


def _check_cacheless_decode(eqn, entrypoint: str,
                            findings: list[Finding]) -> None:
    """J110 for one decode-marked pjit equation: the per-token step
    contains a full-sequence attention softmax, i.e. it recomputes every
    previous position's scores to emit ONE token. One finding per marked
    program (the per-layer repeats add nothing)."""
    body = eqn.params.get("jaxpr")
    if body is None:
        return
    hit = _find_wide_softmax_exp(body)
    if hit is None:
        return
    exp_eqn, shape = hit
    f, ln = _src_loc(exp_eqn)
    findings.append(Finding(
        "J110",
        f"decode step recomputes full-sequence attention per emitted "
        f"token: softmax exp over {list(shape)} scores (query and key "
        f"dims both > 1) inside the per-token program — O(T²) per token; "
        f"carry a KV cache (tpudml.serve) so decode attends [B, H, 1, L]",
        file=f, line=ln, entrypoint=entrypoint,
    ))


def _find_pool_wide_exp(obj, pool_rows: frozenset):
    """First ``exp`` equation (recursing through sub-jaxprs) whose operand's
    LAST dim equals some pool's total row count — attention scores keyed
    over every page in the pool instead of one slot's table window."""
    jaxpr, _ = _inner_jaxpr(obj)
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "exp":
            shape = tuple(
                getattr(getattr(eqn.invars[0], "aval", None), "shape", ())
            )
            if shape and shape[-1] in pool_rows:
                return eqn, shape
        for sub, _extra in _sub_jaxprs(eqn):
            hit = _find_pool_wide_exp(sub, pool_rows)
            if hit is not None:
                return hit
    return None


def _check_full_pool_gather(eqn, entrypoint: str,
                            findings: list[Finding]) -> None:
    """J117 for one paged-decode-marked pjit equation: a healthy paged
    step's softmax is keyed on ``max_pages·page_size`` gathered table
    rows per slot; keying on ``num_pages·page_size`` (the leading-dims
    product of a rank-4 pool invar) means the program materializes the
    WHOLE pool per token — attention cost scaling with total HBM
    provisioned instead of one tenant's window.

    Detectability bound (documented, like J110's): the pool is
    identified shape-wise as any rank-4 invar with both leading dims
    > 1, so the check needs the pool strictly larger than one slot's
    table (num_pages > max_pages — true of any multi-tenant pool; the
    registered entrypoint and fixtures guarantee it) and, for spec
    programs whose DENSE caches are also rank-4, slots >= 2 (else
    slots·max_len collides with the draft's own max_len softmax width).
    One finding per marked program."""
    body = eqn.params.get("jaxpr")
    if body is None:
        return
    jaxpr, _ = _inner_jaxpr(body)
    pool_rows = set()
    for iv in jaxpr.invars:
        shape = tuple(getattr(getattr(iv, "aval", None), "shape", ()))
        if len(shape) == 4 and shape[0] > 1 and shape[1] > 1:
            pool_rows.add(shape[0] * shape[1])
    if not pool_rows:
        return
    hit = _find_pool_wide_exp(body, frozenset(pool_rows))
    if hit is None:
        return
    exp_eqn, shape = hit
    f, ln = _src_loc(exp_eqn)
    findings.append(Finding(
        "J117",
        f"paged decode step attends over the full page pool: softmax exp "
        f"over {list(shape)} scores whose key dim matches a pool's total "
        f"rows (num_pages·page_size) — per-token cost scales with pool "
        f"HBM, not the slot's table window",
        file=f, line=ln, entrypoint=entrypoint,
    ))


def _scan_unfused_tail(obj, dot_dims: set, hits: list) -> None:
    """Recursive in-order scan for J119's tail half: collect the last
    output dim of every ``dot_general`` seen so far, and record any
    ``argmax`` that reduces its operand's LAST axis when that axis's
    size matches a collected matmul output dim — the greedy pick
    consuming a materialized full-width logits row. Sub-pjits named in
    ``FUSED_HEAD_NAMES`` are skipped wholesale: their internal argmax is
    the fused epilogue, not a round-trip."""
    jaxpr, _ = _inner_jaxpr(obj)
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if (name == "jit"
                and str(eqn.params.get("name", "")) in FUSED_HEAD_NAMES):
            continue
        if name == "dot_general":
            for ov in eqn.outvars:
                shape = tuple(getattr(getattr(ov, "aval", None), "shape", ()))
                if shape:
                    dot_dims.add(shape[-1])
        if name == "argmax":
            shape = tuple(
                getattr(getattr(eqn.invars[0], "aval", None), "shape", ())
            )
            axes = tuple(eqn.params.get("axes", ()))
            if (shape and axes and axes == (len(shape) - 1,)
                    and shape[-1] > 1 and shape[-1] in dot_dims):
                hits.append((eqn, shape))
        for sub, _extra in _sub_jaxprs(eqn):
            _scan_unfused_tail(sub, dot_dims, hits)


def _check_unfused_decode_tail(eqn, entrypoint: str,
                               findings: list[Finding]) -> None:
    """J119 (tail half) for one decode-marked pjit equation: the step
    materializes the full-vocab logits row out of the head matmul and
    argmaxes it as a separate reduction — a [B, V] HBM round-trip per
    emitted token that the fused head (``ops.fused_decode_head``) folds
    into the matmul's epilogue. Vocab is identified as any matmul output
    last-dim seen earlier in the same marked body (the head is the only
    matmul whose output width the pick reduces over). One finding per
    marked program."""
    body = eqn.params.get("jaxpr")
    if body is None:
        return
    hits: list = []
    _scan_unfused_tail(body, set(), hits)
    if not hits:
        return
    am_eqn, shape = hits[0]
    f, ln = _src_loc(am_eqn)
    findings.append(Finding(
        "J119",
        f"decode step materializes the full-vocab logits and argmaxes "
        f"them outside the head matmul: argmax over {list(shape)} whose "
        f"reduced dim matches a matmul output width — the [B, V] tail "
        f"round-trips HBM every emitted token",
        file=f, line=ln, entrypoint=entrypoint,
    ))


def _contains_pjit_named(obj, names: tuple) -> bool:
    """True if any (recursively nested) pjit equation carries one of the
    marker ``names`` — J119's overlap-claim verification."""
    jaxpr, _ = _inner_jaxpr(obj)
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "jit"
                and str(eqn.params.get("name", "")) in names):
            return True
        for sub, _extra in _sub_jaxprs(eqn):
            if _contains_pjit_named(sub, names):
                return True
    return False


def _scan_update_collectives(obj, axes: tuple[str, ...], acc: dict) -> None:
    """Recursively collect, for J108: the output shapes of tensor psums
    over any of ``axes`` (the allreduced gradients), and whether any
    reduce-scatter over those axes occurs (the ZeRO-1 signature)."""
    jaxpr, _ = _inner_jaxpr(obj)
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in ("psum", "psum_scatter", "reduce_scatter"):
            eq_axes = _eqn_axes(eqn)
            if any(a in eq_axes for a in axes):
                if name == "psum":
                    for ov in eqn.outvars:
                        shape = tuple(
                            getattr(getattr(ov, "aval", None), "shape", ())
                        )
                        if shape:
                            acc["psum_shapes"].append(shape)
                            if "loc" not in acc:
                                # The shard_map eqn itself carries the
                                # re-trace frame; the first gradient psum
                                # points at the aggregation call site.
                                acc["loc"] = _src_loc(eqn)
                else:
                    acc["rs"] = True
        for sub, _extra in _sub_jaxprs(eqn):
            _scan_update_collectives(sub, axes, acc)


def _check_replicated_update(eqn, entrypoint: str,
                             findings: list[Finding]) -> None:
    """J108 for one shard_map equation: the body allreduces ≥2 tensor
    gradients over a data axis, returns ≥2 matching-shape outputs
    REPLICATED over that axis (per out_specs), and never reduce-scatters
    — i.e. a replicated weight update. A ZeRO-1 body (psum_scatter on
    the grads, state outputs sharded over the axis) stays silent, as
    does a reduce-scatter aggregation strategy."""
    mesh = eqn.params.get("mesh")
    body = eqn.params.get("jaxpr")
    out_names = shard_map_dim_axes(eqn.params.get("out_specs"))
    if mesh is None or body is None:
        return
    axes = tuple(
        a for a in (str(x) for x in mesh.axis_names) if a in _DATA_AXIS_NAMES
    )
    if not axes:
        return
    acc: dict = {"psum_shapes": [], "rs": False}
    _scan_update_collectives(body, axes, acc)
    if acc["rs"] or len(acc["psum_shapes"]) < 2:
        return
    budget: dict[tuple, int] = {}
    for s in acc["psum_shapes"]:
        budget[s] = budget.get(s, 0) + 1
    jaxpr, _ = _inner_jaxpr(body)
    hits = 0
    for var, names in zip(jaxpr.outvars, out_names):
        shape = tuple(getattr(getattr(var, "aval", None), "shape", ()))
        if not shape or budget.get(shape, 0) <= 0:
            continue
        sharded_over = {a for dim_axes in names.values() for a in dim_axes}
        if any(a in sharded_over for a in axes):
            continue
        budget[shape] -= 1
        hits += 1
    if hits >= 2:
        f, ln = acc.get("loc") or _src_loc(eqn)
        findings.append(Finding(
            "J108",
            f"replicated optimizer update under shard_map over data axis "
            f"{list(axes)}: {hits} allreduced gradient-shaped tensors "
            f"return replicated with no reduce-scatter — every chip "
            f"applies the FULL weight update (N× optimizer FLOPs and "
            f"state HBM); ZeRO-1 (optim.zero1) shards it",
            file=f, line=ln, entrypoint=entrypoint,
        ))


def _has_isfinite(obj) -> bool:
    """True if ``is_finite`` appears anywhere in the program (J111's
    silence condition — the sentinel's in-graph grad check)."""
    jaxpr, _ = _inner_jaxpr(obj)
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "is_finite":
            return True
        for sub, _extra in _sub_jaxprs(eqn):
            if _has_isfinite(sub):
                return True
    return False


def _count_param_update_subs(obj, acc: dict) -> None:
    """Count, per jaxpr level, elementwise ``sub`` equations whose
    minuend is taint-derived from one of THAT level's invars through
    shape-repartitioning ops only — the ``p - update`` signature of an
    optimizer step (params enter every level as invars; activations lose
    the taint at the first dot/conv/reduce)."""
    jaxpr, _ = _inner_jaxpr(obj)
    tainted = set(id(v) for v in jaxpr.invars)
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        in_tainted = any(
            id(v) in tainted for v in eqn.invars if hasattr(v, "aval")
        )
        if name in _J111_PRESERVING and in_tainted:
            tainted.update(id(v) for v in eqn.outvars)
        elif name == "sub" and eqn.invars:
            op0 = eqn.invars[0]
            shape = tuple(getattr(getattr(op0, "aval", None), "shape", ()))
            out_shape = tuple(
                getattr(getattr(eqn.outvars[0], "aval", None), "shape", ())
            )
            if (
                id(op0) in tainted
                and shape
                and shape == out_shape
            ):
                acc["count"] += 1
                f, ln = _src_loc(eqn)
                per_file = acc["by_file"].setdefault(f, [0, ln])
                per_file[0] += 1
        for sub, _extra in _sub_jaxprs(eqn):
            _count_param_update_subs(sub, acc)


def _check_unguarded_update(closed, entrypoint: str,
                            findings: list[Finding]) -> None:
    """J111 for one traced program: it writes parameters (≥2 invar-
    derived elementwise subs) yet never evaluates ``is_finite`` — no
    finiteness gate stands between the gradients and the weights."""
    acc: dict = {"count": 0, "by_file": {}}
    _count_param_update_subs(closed, acc)
    if acc["count"] < 2 or _has_isfinite(closed):
        return
    # Anchor at the file contributing the MOST update subs — the
    # optimizer itself, not an incidental tainted sub elsewhere (a loss
    # kernel's shift-by-max on a weight invar) — so one allowlist entry
    # covers every engine sharing that optimizer.
    f, (_, ln) = max(acc["by_file"].items(), key=lambda kv: kv[1][0])
    findings.append(Finding(
        "J111",
        f"optimizer update writes {acc['count']} parameter tensors "
        f"(invar-derived elementwise subs) but the step evaluates no "
        f"is_finite predicate — a single non-finite microbatch reaches "
        f"the weights on every replica at once",
        file=f, line=ln, entrypoint=entrypoint,
    ))


def _check_donated_reuse(jaxpr, entrypoint: str,
                         findings: list[Finding]) -> None:
    """J114: a var donated into a pjit is read again at the same level.

    ``donate_argnums`` tells XLA it may alias the argument's buffer to
    an output; a read after the donating call (a later equation) or a
    second occurrence among the same call's arguments observes clobbered
    memory. A donated invar appearing directly in the enclosing
    program's outvars is NOT flagged: that is jax forwarding an
    unmodified input to an output (common for cache slots a step leaves
    untouched), not a host-level reuse.
    """
    for idx, eqn in enumerate(jaxpr.eqns):
        donated = eqn.params.get("donated_invars")
        if eqn.primitive.name != "jit" or not donated or not any(donated):
            continue
        callee = str(eqn.params.get("name", "")) or "<anonymous>"
        for pos, (v, don) in enumerate(zip(eqn.invars, donated)):
            if not don or hasattr(v, "val"):
                continue
            reuse = None
            if any(v is w for j, w in enumerate(eqn.invars)
                   if j != pos):
                reuse = f"passed again to the same call '{callee}'"
            else:
                for later in jaxpr.eqns[idx + 1:]:
                    if any(v is w for w in later.invars):
                        reuse = (f"consumed again by a later "
                                 f"'{later.primitive.name}' equation")
                        break
            if reuse:
                f, ln = _src_loc(eqn)
                findings.append(Finding(
                    "J114",
                    f"argument {pos} is donated to jitted call '{callee}' "
                    f"but its buffer is {reuse} — XLA may alias donated "
                    f"memory to an output, so the second read observes "
                    f"overwritten bytes",
                    file=f, line=ln, entrypoint=entrypoint,
                ))


def _walk(obj, bound: frozenset[str], entrypoint: str,
          findings: list[Finding]) -> None:
    jaxpr, consts = _inner_jaxpr(obj)
    _check_consts(consts, entrypoint, findings)
    _check_upcasts(jaxpr, entrypoint, findings)
    _check_ragged_transpose(jaxpr, entrypoint, findings)
    _check_donated_reuse(jaxpr, entrypoint, findings)
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            missing = [a for a in _eqn_axes(eqn) if a not in bound]
            if missing:
                f, ln = _src_loc(eqn)
                findings.append(Finding(
                    "J101",
                    f"{name} over axis {missing} but enclosing "
                    f"shard_map/pmap binds {sorted(bound) or 'no axes'}",
                    file=f, line=ln, entrypoint=entrypoint,
                ))
        if name in CALLBACK_PRIMS:
            f, ln = _src_loc(eqn)
            cb = eqn.params.get("callback", None)
            detail = f" ({getattr(cb, '__name__', cb)})" if cb is not None else ""
            findings.append(Finding(
                "J103",
                f"host callback primitive {name}{detail} inside the "
                f"jitted step",
                file=f, line=ln, entrypoint=entrypoint,
            ))
        if name == "cond":
            branches = eqn.params.get("branches", ())
            sigs = [_collective_signature(b) for b in branches]
            if sigs and any(s != sigs[0] for s in sigs[1:]):
                f, ln = _src_loc(eqn)
                desc = "; ".join(
                    f"branch {i}: " + (
                        ", ".join(p for p, _ in s) if s else "<none>")
                    for i, s in enumerate(sigs)
                )
                findings.append(Finding(
                    "J102",
                    f"cond/switch branches issue different collective "
                    f"sequences — {desc}",
                    file=f, line=ln, entrypoint=entrypoint,
                ))
        if name == "jit" and str(eqn.params.get("name", "")) == SERVE_DECODE_NAME:
            _check_cacheless_decode(eqn, entrypoint, findings)
        if name == "jit" and str(eqn.params.get("name", "")) in PAGED_DECODE_NAMES:
            _check_full_pool_gather(eqn, entrypoint, findings)
        if name == "jit" and str(eqn.params.get("name", "")) in _DECODE_TAIL_NAMES:
            _check_unfused_decode_tail(eqn, entrypoint, findings)
        if name == "shard_map":
            seed = _fused_xent_seed(eqn)
            if seed:
                _check_fused_xent(eqn.params["jaxpr"], seed, entrypoint,
                                  findings)
            _check_replicated_update(eqn, entrypoint, findings)
        for sub, extra in _sub_jaxprs(eqn):
            _walk(sub, bound | extra, entrypoint, findings)


def _check_consts(consts, entrypoint: str, findings: list[Finding]) -> None:
    import numpy as np

    for c in consts:
        shape = getattr(c, "shape", None)
        dtype = getattr(c, "dtype", None)
        if shape is None or dtype is None:
            continue
        # Closed-over constants are jax TypedNdArrays, which carry
        # shape/dtype but no ``nbytes``.
        nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
        if nbytes > LARGE_CONST_BYTES:
            findings.append(Finding(
                "J105",
                f"{nbytes / (1 << 20):.1f} MiB constant "
                f"({dtype}{list(shape)}) captured by closure — pass it as "
                f"a (donatable) argument instead",
                entrypoint=entrypoint,
            ))


def analyze_closed_jaxpr(
    closed,
    entrypoint: str = "",
    in_specs=None,
    mesh_axes: dict[str, int] | None = None,
    hbm_budget_bytes: int | None = None,
    plan: dict | None = None,
) -> list[Finding]:
    """All jaxpr-level findings (J101-J105, J107-J118) for one traced
    program: the local pattern rules plus the replication-lattice
    dataflow rules. ``in_specs``/``mesh_axes`` seed the interpreter's
    top-level states (engines attach them to their jitted steps);
    ``hbm_budget_bytes`` arms J116; ``plan`` (a plan.json document)
    arms J118 — traced comm/HBM vs the plan's ``predicted`` block."""
    from tpudml.analysis.cost import (
        check_hbm_budget,
        check_plan_drift,
        summarize_cost,
    )
    from tpudml.analysis.dataflow import analyze_dataflow

    findings: list[Finding] = []
    _walk(closed, frozenset(), entrypoint, findings)
    _check_unguarded_update(closed, entrypoint, findings)
    flow = analyze_dataflow(closed, entrypoint, in_specs=in_specs,
                            mesh_axes=mesh_axes)
    findings.extend(flow.findings)
    if hbm_budget_bytes or plan is not None:
        cost = summarize_cost(entrypoint, flow, closed)
        if hbm_budget_bytes:
            findings.extend(check_hbm_budget(cost, hbm_budget_bytes))
        if plan is not None:
            findings.extend(check_plan_drift(cost, plan))
    if plan is not None:
        cand = ((plan.get("winner") or {}).get("candidate") or {})
        if cand.get("tp_overlap") and not _contains_pjit_named(
                closed, (TP_OVERLAP_NAME,)):
            findings.append(Finding(
                "J119",
                f"plan winner {cand.get('key', '?')} claims psum-"
                f"overlapped TP matmuls (tp_overlap) but the traced "
                f"program carries no {TP_OVERLAP_NAME} marker — the wire "
                f"time the plan priced as hidden is actually exposed on "
                f"the critical path",
                entrypoint=entrypoint,
            ))
    return findings


# ------------------------------------------------------------- donation

_MAIN_SIG_RE = re.compile(
    r"func\.func public @main\((.*?)\)\s*->", re.DOTALL)
_ARG_RE = re.compile(r"%arg\d+: tensor<([^>]*)>\s*(\{[^}]*\})?")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "i64": 8, "ui64": 8, "i32": 4, "ui32": 4, "i16": 2, "ui16": 2,
    "i8": 1, "ui8": 1, "i1": 1, "c64": 8, "c128": 16,
}


def _tensor_bytes(spec: str) -> int:
    parts = spec.strip().split("x")
    dtype = parts[-1]
    n = 1
    for d in parts[:-1]:
        try:
            n *= int(d)
        except ValueError:  # dynamic dim — treat as 1
            pass
    return n * _DTYPE_BYTES.get(dtype, 4)


def donation_findings(
    lowered_text: str,
    entrypoint: str = "",
    min_bytes: int = LARGE_CONST_BYTES,
) -> list[Finding]:
    """J106 from a lowered StableHLO module: large entry args with no
    donation aliasing anywhere. (Per-arg precision is deliberate-ly NOT
    attempted — batch inputs legitimately go undonated; the hazard is a
    step whose whole TrainState is undonated, i.e. zero aliased args.)"""
    m = _MAIN_SIG_RE.search(lowered_text)
    if not m:
        return []
    donated_bytes = 0
    undonated_large = 0
    undonated_bytes = 0
    for spec, attrs in _ARG_RE.findall(m.group(1)):
        nbytes = _tensor_bytes(spec)
        if attrs and ("tf.aliasing_output" in attrs
                      or "jax.buffer_donor" in attrs):
            donated_bytes += nbytes
        elif nbytes >= min_bytes:
            undonated_large += 1
            undonated_bytes += nbytes
    if donated_bytes == 0 and undonated_large > 0:
        return [Finding(
            "J106",
            f"{undonated_bytes / (1 << 20):.1f} MiB across "
            f"{undonated_large} large input(s) and no argument is donated "
            f"— params/opt-state double-buffer every step",
            entrypoint=entrypoint,
        )]
    return []


# ----------------------------------------------------------- callable API

def analyze_callable(
    fn: Callable,
    args: tuple,
    entrypoint: str = "",
    expects_donation: bool = False,
    in_specs=None,
    mesh_axes: dict[str, int] | None = None,
    hbm_budget_bytes: int | None = None,
    plan: dict | None = None,
) -> list[Finding]:
    """Trace ``fn(*args)`` abstractly and run every jaxpr rule on it.

    Unbound-axis collectives abort the trace itself (JAX raises
    ``NameError`` at bind time), so that failure mode is caught here and
    reported as J101 rather than ever reaching ``_walk``. Other trace
    failures surface as J100 — a step that cannot even abstract-eval
    will not run on the chip either.
    """
    import jax

    try:
        closed = jax.make_jaxpr(fn)(*args)
    except NameError as e:
        if "unbound axis name" in str(e):
            return [Finding(
                "J101",
                f"trace failed: {e} — collective issued outside any "
                f"shard_map/pmap binding that axis",
                entrypoint=entrypoint,
            )]
        return [Finding("J100", f"trace failed: {e!r}", entrypoint=entrypoint)]
    except Exception as e:  # noqa: BLE001 - converted to a finding
        return [Finding("J100", f"trace failed: {e!r}", entrypoint=entrypoint)]
    findings = analyze_closed_jaxpr(
        closed, entrypoint, in_specs=in_specs, mesh_axes=mesh_axes,
        hbm_budget_bytes=hbm_budget_bytes, plan=plan)
    if expects_donation and hasattr(fn, "lower"):
        try:
            text = fn.lower(*args).as_text()
        except Exception as e:  # noqa: BLE001 - converted to a finding
            findings.append(Finding(
                "J100", f"lowering failed: {e!r}", entrypoint=entrypoint))
        else:
            findings.extend(donation_findings(text, entrypoint))
    return findings
