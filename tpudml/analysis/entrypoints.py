"""Registry of traceable train-step entrypoints for the jaxpr pass.

Each builder constructs the *real* engine from the parallel layer — the
same classes the tasks instantiate — around a deliberately tiny model,
then hands back the raw jitted program (``step.jitted``, attached by
every engine's ``make_train_step``) plus matching abstract-shaped
inputs. Tracing that program on CPU walks the identical jaxpr that
would lower for a TPU slice: shard_map axis bindings, collectives,
donation annotations and all. Nothing here requires accelerator
hardware, only >= 2 visible devices (the CLI forces an 8-device host
platform before importing jax; the test suite's conftest does the same).

Coverage vs the parallel layer:

==============  =====================================  ================
entrypoint      engine / step builder                  task analogue
==============  =====================================  ================
task1_single    tpudml.train.make_train_step           task1
task2_dp        parallel/dp.py DataParallel (fused)    task2, task3
dp_zero1        DataParallel + ZeRO-1 sharded update   task2 --zero1
dp_sentinel     dp_zero1 + in-graph step sentinel      task2 --sentinel
task4_mp        parallel/mp.py GSPMDParallel           task4
fsdp            parallel/fsdp.py FSDP                  task5 --mode fsdp
tp_fused        GSPMDParallel + sharded fused head     task5 tp --fused_xent
fsdp_fused      FSDP + sharded fused head              task5 fsdp --fused_xent
pp_gpipe        parallel/pp.py GPipe                   task5 --mode pp
cp_ring         parallel/cp.py ContextParallel         task5 --mode cp
ep_moe          parallel/ep.py ExpertParallel          task5 --mode ep
lm_bf16         make_train_step on a bf16 LM           task5 --mode single
serve_decode    serve/engine.py make_decode_step       task6
serve_paged     serve/engine.py make_paged_decode_step task6 --paged
==============  =====================================  ================

(``serve_paged`` is registered as ``serve_paged_decode``.)
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

from tpudml.analysis.findings import Finding
from tpudml.analysis.jaxpr_pass import analyze_callable


@dataclass(frozen=True)
class Program:
    """One traceable device program: a jitted callable + example args.

    ``in_specs``/``mesh_axes`` (when the engine attaches them to its
    step next to ``.jitted``) seed the dataflow interpreter's top-level
    replication states and the ``--cost`` per-device arithmetic; both
    default to None for mesh-less single-device programs.
    """

    name: str
    fn: Callable
    args: tuple
    expects_donation: bool = True
    in_specs: tuple | None = None
    mesh_axes: dict | None = None


def _program(name: str, step, args: tuple, **kw) -> Program:
    """Build a Program from an engine step, lifting the in_spec metadata
    the engines attach next to ``.jitted``."""
    return Program(
        name, step.jitted, args,
        in_specs=getattr(step, "in_specs", None),
        mesh_axes=getattr(step, "mesh_axes", None),
        **kw,
    )


def _np():
    import numpy as np
    return np


def _mesh(axis: str, size: int):
    import jax
    from tpudml.core.config import MeshConfig
    from tpudml.core.dist import make_mesh

    if len(jax.devices()) < size:
        raise RuntimeError(
            f"need {size} devices for axis '{axis}', have "
            f"{len(jax.devices())} — set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8")
    return make_mesh(MeshConfig({axis: size}), jax.devices()[:size])


def _lenet_batch(n=4):
    np = _np()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, 28, 28, 1)).astype(np.float32)
    y = rng.integers(0, 10, size=(n,)).astype(np.int32)
    return x, y


def _lm_batch(b=2, t=8, vocab=32):
    np = _np()
    rng = np.random.default_rng(0)
    seqs = rng.integers(0, vocab, size=(b, t + 1)).astype(np.int32)
    return seqs[:, :-1], seqs[:, 1:]


def _tiny_lm(**kw):
    from tpudml.models import TransformerLM

    base = dict(vocab_size=32, embed_dim=16, num_heads=2, num_layers=1,
                max_len=8)
    base.update(kw)
    return TransformerLM(**base)


def build_task1_single() -> list[Program]:
    from tpudml.core.prng import seed_key
    from tpudml.models import LeNet
    from tpudml.optim import make_optimizer
    from tpudml.train import TrainState, make_train_step

    model, opt = LeNet(), make_optimizer("sgd", 0.01)
    ts = TrainState.create(model, opt, seed_key(0))
    step = make_train_step(model, opt)  # already the jitted program
    x, y = _lenet_batch()
    return [Program("task1_single", step, (ts, x, y))]


def build_task2_dp() -> list[Program]:
    from tpudml.core.prng import seed_key
    from tpudml.models import LeNet
    from tpudml.optim import make_optimizer
    from tpudml.parallel.dp import DataParallel

    dp = DataParallel(LeNet(), make_optimizer("sgd", 0.01), _mesh("data", 2))
    ts = dp.create_state(seed_key(0))
    step = dp.make_train_step()
    x, y = _lenet_batch()
    return [_program("task2_dp", step, (ts, x, y))]


def build_dp_zero1() -> list[Program]:
    """Data parallelism with the ZeRO-1 weight-update shard: the traced
    step must reduce-scatter the gradients and all-gather the params
    (J108 stays silent — the psum_scatter is the whole point)."""
    from tpudml.core.prng import seed_key
    from tpudml.models import LeNet
    from tpudml.optim import make_optimizer
    from tpudml.parallel.dp import DataParallel

    dp = DataParallel(LeNet(), make_optimizer("adam", 1e-3),
                      _mesh("data", 2), zero1=True)
    ts = dp.create_state(seed_key(0))
    step = dp.make_train_step()
    x, y = _lenet_batch()
    return [_program("dp_zero1", step, (ts, x, y))]


def build_dp_sentinel() -> list[Program]:
    """ZeRO-1 data parallelism with the in-graph step sentinel: the
    traced step carries an ``is_finite`` gate between the gradients and
    the update, so J111 stays silent here (and J108 stays silent via the
    reduce-scatter) — the guarded counterpart of the plain engines the
    rule fires on."""
    from tpudml.core.prng import seed_key
    from tpudml.models import LeNet
    from tpudml.optim import make_optimizer
    from tpudml.parallel.dp import DataParallel

    dp = DataParallel(LeNet(), make_optimizer("adam", 1e-3),
                      _mesh("data", 2), zero1=True, sentinel=True)
    ts = dp.create_state(seed_key(0))
    step = dp.make_train_step()
    x, y = _lenet_batch()
    return [_program("dp_sentinel", step, (ts, x, y))]


def build_task4_mp() -> list[Program]:
    from tpudml.core.prng import seed_key
    from tpudml.models import LeNet
    from tpudml.optim import make_optimizer
    from tpudml.parallel.mp import GSPMDParallel

    mp = GSPMDParallel(LeNet(), make_optimizer("sgd", 0.01),
                       _mesh("stage", 2))
    ts = mp.create_state(seed_key(0))
    step = mp.make_train_step()
    x, y = _lenet_batch()
    return [_program("task4_mp", step, (ts, x, y))]


def build_fsdp() -> list[Program]:
    from tpudml.core.prng import seed_key
    from tpudml.models import ForwardMLP
    from tpudml.optim import make_optimizer
    from tpudml.parallel.fsdp import FSDP

    eng = FSDP(ForwardMLP(), make_optimizer("adam", 1e-3), _mesh("data", 2))
    ts = eng.create_state(seed_key(0))
    step = eng.make_train_step()
    np = _np()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(4, 784)).astype(np.float32)
    y = rng.integers(0, 10, size=(4,)).astype(np.int32)
    return [_program("fsdp", step, (ts, x, y))]


def build_tp_fused() -> list[Program]:
    """Tensor parallelism with the vocab-sharded fused head: the traced
    step must carry the SHARDED marker (J107 stays silent) and the lse
    merge collectives inside the shard_map loss region."""
    from tpudml.core.prng import seed_key
    from tpudml.optim import make_optimizer
    from tpudml.parallel.mp import GSPMDParallel, tensor_parallel_rules

    eng = GSPMDParallel(
        _tiny_lm(), make_optimizer("sgd", 0.05), _mesh("model", 2),
        rule=tensor_parallel_rules("model"), axis_name="model",
        fused_xent=True,
    )
    ts = eng.create_state(seed_key(0))
    step = eng.make_train_step()
    x, y = _lm_batch()
    return [_program("tp_fused", step, (ts, x, y))]


def build_fsdp_fused() -> list[Program]:
    """1-D FSDP with the fused head: vocab and tokens share the data
    axis, so the loss region all-gathers the batch and merges vocab
    statistics over the same axis."""
    from tpudml.core.prng import seed_key
    from tpudml.optim import make_optimizer
    from tpudml.parallel.fsdp import FSDP

    eng = FSDP(_tiny_lm(), make_optimizer("sgd", 0.05), _mesh("data", 2),
               fused_xent=True)
    ts = eng.create_state(seed_key(0))
    step = eng.make_train_step()
    x, y = _lm_batch()
    return [_program("fsdp_fused", step, (ts, x, y))]


def build_pp_gpipe() -> list[Program]:
    import jax
    from tpudml.core.prng import seed_key
    from tpudml.nn.layers import Activation, Dense, Sequential
    from tpudml.optim import make_optimizer
    from tpudml.parallel.pp import GPipe

    pipe = GPipe(
        Sequential((Dense(8, 8), Activation(jax.nn.relu))),
        n_microbatches=2,
        mesh=_mesh("stage", 2),
        optimizer=make_optimizer("sgd", 0.05),
        prologue=Dense(4, 8),
        epilogue=Dense(8, 4),
    )
    ts = pipe.create_state(seed_key(0))
    step = pipe.make_train_step()
    np = _np()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(4, 4)).astype(np.float32)
    y = rng.integers(0, 4, size=(4,)).astype(np.int32)
    return [_program("pp_gpipe", step, (ts, x, y))]


def build_cp_ring() -> list[Program]:
    from tpudml.core.prng import seed_key
    from tpudml.optim import make_optimizer
    from tpudml.parallel.cp import ContextParallel

    lm = _tiny_lm(impl="ring", seq_sharded=True)
    cp = ContextParallel(lm, make_optimizer("sgd", 0.1), _mesh("seq", 2))
    ts = cp.create_state(seed_key(0))
    step = cp.make_train_step()
    x, y = _lm_batch()
    return [_program("cp_ring", step, (ts, x, y))]


def build_ep_moe() -> list[Program]:
    from tpudml.core.prng import seed_key
    from tpudml.optim import make_optimizer
    from tpudml.parallel.ep import ExpertParallel

    lm = _tiny_lm(moe_experts=2, moe_axis="expert")
    ep = ExpertParallel(lm, make_optimizer("adam", 0.01), _mesh("expert", 2))
    ts = ep.create_state(seed_key(0))
    step = ep.make_train_step()
    x, y = _lm_batch()
    return [_program("ep_moe", step, (ts, x, y))]


def build_lm_bf16() -> list[Program]:
    import jax.numpy as jnp
    from tpudml.core.prng import seed_key
    from tpudml.optim import make_optimizer
    from tpudml.train import TrainState, make_train_step

    lm = _tiny_lm(dtype=jnp.bfloat16)
    opt = make_optimizer("sgd", 0.01)
    ts = TrainState.create(lm, opt, seed_key(0))
    step = make_train_step(lm, opt)
    x, y = _lm_batch()
    return [Program("lm_bf16", step, (ts, x, y))]


def build_moe_ragged() -> list[Program]:
    """Single-shard dropless ragged MoE — the surface J109 guards. The
    default grouped-dW backward must trace J109-silent; flipping
    moe_ragged_dw='stock' here is the rule's firing fixture (covered in
    tests/test_analysis.py, not registered as an entrypoint)."""
    from tpudml.core.prng import seed_key
    from tpudml.optim import make_optimizer
    from tpudml.train import TrainState, make_train_step

    lm = _tiny_lm(moe_experts=2, moe_dispatch="ragged")
    opt = make_optimizer("adam", 0.01)
    ts = TrainState.create(lm, opt, seed_key(0))
    step = make_train_step(lm, opt)
    x, y = _lm_batch()
    return [Program("moe_ragged", step, (ts, x, y))]


def build_serve_decode() -> list[Program]:
    """The serving engine's jitted per-token decode step — the surface
    J110 guards. The cache-carrying step must trace J110-silent (its
    softmax is [B, H, 1, L]); the rule's firing fixture is
    ``tests/analysis_fixtures/cacheless_decode.py`` (covered in
    tests/test_analysis.py, not registered as an entrypoint)."""
    import jax
    from tpudml.serve import ServeConfig, ServingEngine

    lm = _tiny_lm(rope=True, num_kv_heads=1)
    params, _ = lm.init(jax.random.key(0))
    eng = ServingEngine(
        lm, params,
        ServeConfig(slots=2, max_len=8, prefill_chunk=4),
    )
    np = _np()
    tokens = np.zeros(2, np.int32)
    pos = np.zeros(2, np.int32)
    return [Program(
        "serve_decode", eng._decode, (params, eng.caches, tokens, pos),
        # The donated buffers are the per-layer KV caches — a few KiB at
        # this toy size, far under the J106 large-input threshold — so
        # lowering-level donation analysis has nothing to check here.
        expects_donation=False,
    )]


def build_serve_paged_decode() -> list[Program]:
    """The paged serving engine's jitted decode step — the surface J117
    guards. The table-gathering step must trace J117-silent (its softmax
    keys on max_pages·page_size gathered rows); a step that broadcasts
    the whole pool per token is the rule's firing fixture (covered in
    tests/analysis_fixtures/jaxpr/, not registered). ``num_pages`` is
    chosen strictly above one slot's table (5 > 4) so pool rows and
    table rows cannot collide shape-wise — the rule's documented
    detectability bound."""
    import jax
    import numpy as np
    from tpudml.serve import ServeConfig, ServingEngine

    lm = _tiny_lm(rope=True, num_kv_heads=1)
    params, _ = lm.init(jax.random.key(0))
    eng = ServingEngine(
        lm, params,
        ServeConfig(slots=2, max_len=8, prefill_chunk=4,
                    cache_layout="paged", page_size=2, num_pages=5),
    )
    tokens = np.zeros(2, np.int32)
    pos = np.zeros(2, np.int32)
    table = np.zeros((2, eng.cfg.max_pages), np.int32)
    return [Program(
        "serve_paged_decode", eng._decode,
        (params, eng.caches, table, tokens, pos),
        expects_donation=False,  # donated pool is KiB-scale, like serve_decode
    )]


def build_serve_fused() -> list[Program]:
    """The dense decode step with the fused head tail
    (``ServeConfig(fused_head=True)``) — the surface J119's tail check
    guards. The fused step must trace J119-silent: its greedy pick lives
    INSIDE the ``_fused_decode_head`` marker pjit, which the scan skips;
    the plain ``serve_decode`` entrypoint above is the rule's
    (allowlisted) firing fixture."""
    import jax
    from tpudml.serve import ServeConfig, ServingEngine

    lm = _tiny_lm(rope=True, num_kv_heads=1)
    params, _ = lm.init(jax.random.key(0))
    eng = ServingEngine(
        lm, params,
        ServeConfig(slots=2, max_len=8, prefill_chunk=4, fused_head=True),
    )
    np = _np()
    tokens = np.zeros(2, np.int32)
    pos = np.zeros(2, np.int32)
    return [Program(
        "serve_fused", eng._decode, (params, eng.caches, tokens, pos),
        expects_donation=False,  # KiB-scale caches, like serve_decode
    )]


#: name -> builder; order is reporting order.
ENTRYPOINTS: dict[str, Callable[[], list[Program]]] = {
    "task1_single": build_task1_single,
    "task2_dp": build_task2_dp,
    "dp_zero1": build_dp_zero1,
    "dp_sentinel": build_dp_sentinel,
    "task4_mp": build_task4_mp,
    "fsdp": build_fsdp,
    "tp_fused": build_tp_fused,
    "fsdp_fused": build_fsdp_fused,
    "pp_gpipe": build_pp_gpipe,
    "cp_ring": build_cp_ring,
    "ep_moe": build_ep_moe,
    "moe_ragged": build_moe_ragged,
    "lm_bf16": build_lm_bf16,
    "serve_decode": build_serve_decode,
    "serve_paged_decode": build_serve_paged_decode,
    "serve_fused": build_serve_fused,
}


def analyze_entrypoint(
    name: str, hbm_budget_bytes: int | None = None
) -> list[Finding]:
    """Build one entrypoint and run every jaxpr rule on its program(s).

    A builder that raises becomes a J100 finding rather than an
    exception: an entrypoint that cannot even be constructed on CPU is
    itself a pre-flight failure worth reporting.
    """
    builder = ENTRYPOINTS[name]
    try:
        programs = builder()
    except Exception as e:  # noqa: BLE001 - converted to a finding
        return [Finding("J100", f"entrypoint failed to build: {e!r}",
                        entrypoint=name)]
    findings: list[Finding] = []
    for prog in programs:
        findings.extend(analyze_callable(
            prog.fn, prog.args, entrypoint=prog.name,
            expects_donation=prog.expects_donation,
            in_specs=prog.in_specs, mesh_axes=prog.mesh_axes,
            hbm_budget_bytes=hbm_budget_bytes))
    return findings


def analyze_entrypoints(
    names: list[str] | None = None, hbm_budget_bytes: int | None = None
) -> list[Finding]:
    findings: list[Finding] = []
    for name in names or list(ENTRYPOINTS):
        findings.extend(analyze_entrypoint(name, hbm_budget_bytes))
    return findings


def cost_entrypoints(names: list[str] | None = None):
    """Static cost summaries (``--cost``) for the registered entrypoints:
    one dataflow walk + CommEvent aggregation + peak-HBM estimate per
    program. Returns ``(costs, findings)`` — build/trace failures become
    an EntrypointCost carrying ``error`` plus a J100 finding, so the cost
    table never hides a broken entrypoint."""
    import jax

    from tpudml.analysis.cost import EntrypointCost, summarize_cost
    from tpudml.analysis.dataflow import analyze_dataflow

    costs = []
    findings: list[Finding] = []
    for name in names or list(ENTRYPOINTS):
        try:
            programs = ENTRYPOINTS[name]()
        except Exception as e:  # noqa: BLE001 - converted to a finding
            findings.append(Finding(
                "J100", f"entrypoint failed to build: {e!r}",
                entrypoint=name))
            costs.append(EntrypointCost(entrypoint=name, error=repr(e)))
            continue
        for prog in programs:
            try:
                closed = jax.make_jaxpr(prog.fn)(*prog.args)
            except Exception as e:  # noqa: BLE001 - converted to a finding
                findings.append(Finding(
                    "J100", f"trace failed: {e!r}", entrypoint=prog.name))
                costs.append(EntrypointCost(entrypoint=prog.name,
                                            error=repr(e)))
                continue
            flow = analyze_dataflow(closed, prog.name,
                                    in_specs=prog.in_specs,
                                    mesh_axes=prog.mesh_axes)
            findings.extend(flow.findings)
            costs.append(summarize_cost(prog.name, flow, closed))
    return costs, findings
