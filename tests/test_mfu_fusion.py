"""Round-20 MFU-gap fusions: the fused decode tail, the fused train
attention junction, and the psum-overlapped TP matmul.

Load-bearing properties:

- ``fused_decode_head`` (Pallas machinery, interpret mode) emits tokens
  EXACTLY equal to ``argmax(x @ W + b)`` — first-occurrence ties and
  padded vocab tails included — plus the f32 online (max, lse)
  statistics, under plain, row-sharded (DP), vocab-sharded (TP), and
  rows×vocab (FSDP×TP) compositions;
- the int8 variant's greedy picks are bitwise those of the dequantized-
  weights oracle (``serve/fleet/quant.py`` op order), pinned kernel-
  level and end-to-end on the serving engine's fixture prompts;
- ``fused_attn_junction`` is the same function as the unfused block
  junction — values AND gradients at the single-shard parity tolerances
  (rtol=1e-5/atol=1e-6) — standalone and under the sharded regimes;
- ``tp_overlap_matmul`` equals the unchunked ``psum(x @ w)`` in value
  and gradient (the chunk split is over rows the reduce never mixes);
- the train engines' ``flash_attn`` knob changes the attention DISPATCH
  only: DP/TP/FSDP trajectories match the unfused engines exactly on
  CPU (reference-dispatch plumbing, like test_fused_compose's contract)
  and the capability row rejects the ring/ulysses and seq_sharded
  compositions at construction.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from tpudml.core.config import MeshConfig
from tpudml.core.dist import make_mesh
from tpudml.core.prng import seed_key
from tpudml.models import TransformerLM
from tpudml.ops.decode_head import (
    _reference_head,
    fused_decode_head,
    fused_decode_head_int8,
)
from tpudml.ops.junction_kernel import (
    fused_attn_junction,
    reference_attn_junction,
)
from tpudml.optim import make_optimizer
from tpudml.parallel.sharding import shard_map_fn

V = 48


def _model(**kw):
    cfg = dict(vocab_size=V, embed_dim=32, num_heads=4, num_layers=2,
               max_len=64, rope=True)
    cfg.update(kw)
    return TransformerLM(**cfg)


def _assert_tree_close(a, b, rtol=1e-5, atol=1e-6):
    flat_a = jax.tree_util.tree_leaves_with_path(a)
    flat_b = dict(jax.tree_util.tree_leaves_with_path(b))
    for path, la in flat_a:
        np.testing.assert_allclose(
            np.asarray(la), np.asarray(flat_b[path]), rtol=rtol, atol=atol,
            err_msg=jax.tree_util.keystr(path),
        )


# -------------------------------------------------- decode tail: kernel


def _head_operands(n=16, d=8, v=64, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(d, v)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(v,)).astype(np.float32))
    return x, w, b


@pytest.mark.parametrize("v", [64, 70])  # 70: padded vocab tail masked
def test_decode_head_interpret_matches_reference(v):
    x, w, b = _head_operands(v=v)
    tok, mx, lse = fused_decode_head(
        x, w, b, block_n=8, block_v=32, interpret=True)
    rt, rm, rl = _reference_head(x, w, b)
    np.testing.assert_array_equal(np.asarray(tok), np.asarray(rt))
    np.testing.assert_allclose(np.asarray(mx), np.asarray(rm), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(rl),
                               rtol=1e-5, atol=1e-6)


def test_decode_head_first_occurrence_tie_break():
    """Duplicated max columns — including duplicates split ACROSS vocab
    tiles — must resolve to the first occurrence, like jnp.argmax."""
    x = jnp.ones((4, 4), jnp.float32)
    w = jnp.zeros((4, 96), jnp.float32)
    # row of logits all equal -> pick must be column 0; then plant an
    # early max duplicated in a LATER tile (block_v=32: cols 7 and 40).
    w = w.at[:, 7].set(2.0).at[:, 40].set(2.0)
    tok, _, _ = fused_decode_head(
        x, w, None, block_n=8, block_v=32, interpret=True)
    assert np.asarray(tok).tolist() == [7, 7, 7, 7]
    flat = jnp.zeros((4, 96), jnp.float32)
    tok0, _, _ = fused_decode_head(
        x, flat, None, block_n=8, block_v=32, interpret=True)
    assert np.asarray(tok0).tolist() == [0, 0, 0, 0]


def test_decode_head_int8_bitwise_vs_dequant_oracle():
    """The in-kernel per-tile dequant follows the oracle's exact op
    order, so picks AND statistics are bitwise those of the f32 kernel
    on dequantize(wq, scale)."""
    from tpudml.serve.fleet.quant import _dequant_kernel, _quant_kernel

    x, w, b = _head_operands(v=64, seed=3)
    wq, scale = _quant_kernel(w)
    tok, mx, lse = fused_decode_head_int8(
        x, wq, scale, b, block_n=8, block_v=32, interpret=True)
    rt, rm, rl = fused_decode_head(
        x, _dequant_kernel(wq, scale), b, block_n=8, block_v=32,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(tok), np.asarray(rt))
    np.testing.assert_array_equal(np.asarray(mx), np.asarray(rm))
    np.testing.assert_array_equal(np.asarray(lse), np.asarray(rl))


def test_decode_head_sharded_compositions():
    """The fused head under the engine shardings: rows over data (DP),
    vocab over model with an online (m, lse, tok) shard merge (TP), and
    rows×vocab (FSDP×TP) — tokens exact, statistics at parity tolerance
    against the unsharded reference."""
    x, w, b = _head_operands(n=16, d=8, v=64, seed=5)
    rt, rm, rl = _reference_head(x, w, b)

    def check(tok, mx, lse):
        np.testing.assert_array_equal(np.asarray(tok), np.asarray(rt))
        np.testing.assert_allclose(np.asarray(mx), np.asarray(rm), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(rl),
                                   rtol=1e-5, atol=1e-6)

    # DP: rows sharded, everything else replicated — pure map.
    dp = make_mesh(MeshConfig({"data": 4}), jax.devices()[:4])

    def dp_body(x, w, b):
        return fused_decode_head(x, w, b, block_n=8, block_v=32,
                                 interpret=True)

    check(*shard_map_fn(
        dp_body, dp, in_specs=(P("data"), P(), P()),
        out_specs=(P("data"), P("data"), P("data")))(x, w, b))

    # TP: vocab sharded; each shard picks over its slice, then the
    # global pick is the max-logit shard's local pick offset by its
    # vocab base (strict > with index tie-break = first occurrence),
    # and lse merges by the online rule — the same merge the sharded
    # xent head uses for its statistics.
    tp = make_mesh(MeshConfig({"model": 4}), jax.devices()[:4])

    def tp_body(x, w, b):
        v_loc = w.shape[1]
        base = jax.lax.axis_index("model") * v_loc
        tok, m, lse = fused_decode_head(x, w, b, block_n=8, block_v=16,
                                        interpret=True)
        gm = jax.lax.all_gather(m, "model", axis=1)          # [n, S]
        gt = jax.lax.all_gather(tok + base, "model", axis=1)  # [n, S]
        gl = jax.lax.all_gather(lse, "model", axis=1)
        best = jnp.argmax(gm, axis=1)                         # first occ.
        rows = jnp.arange(gm.shape[0])
        mx = gm[rows, best]
        lse = mx + jnp.log(jnp.sum(jnp.exp(gl - mx[:, None]), axis=1))
        return gt[rows, best], mx, lse

    check(*shard_map_fn(
        tp_body, tp,
        in_specs=(P(), P(None, "model"), P("model")),
        out_specs=(P(), P(), P()))(x, w, b))

    # FSDP×TP: rows over data AND vocab over model — the 2-D engine
    # layout; per-row merge identical to TP on the data-local rows.
    ft = make_mesh(MeshConfig({"data": 2, "model": 2}), jax.devices()[:4])
    check(*shard_map_fn(
        tp_body, ft,
        in_specs=(P("data"), P(None, "model"), P("model")),
        out_specs=(P("data"), P("data"), P("data")))(x, w, b))


# --------------------------------------------- decode tail: serve engine


def _fixture_requests():
    """Committed fixture prompts: fixed token ids, not random draws, so
    the greedy streams this file pins are reproducible byte-for-byte."""
    from tpudml.serve import Request

    prompts = [
        [1, 7, 3, 12, 9],
        [40, 2, 2, 31],
        [5, 19, 23, 8, 44, 17],
        [11, 30],
    ]
    return [
        Request(rid=i, prompt=np.asarray(p, np.int32), max_new_tokens=6)
        for i, p in enumerate(prompts)
    ]


def _greedy_streams(model, params, **cfg_kw):
    from tpudml.serve import ServeConfig, ServingEngine

    cfg = ServeConfig(slots=2, max_len=32, prefill_chunk=4, **cfg_kw)
    rep = ServingEngine(model, params, cfg).run(_fixture_requests())
    return {rid: st.tokens for rid, st in rep.requests.items()}


@pytest.fixture(scope="module")
def served():
    model = _model(num_kv_heads=2)
    params, _ = model.init(jax.random.key(0))
    return model, params


def test_engine_fused_head_greedy_parity(served):
    """fused_head=True serves the exact unfused token streams on the
    fixture prompts (greedy decode is a pure function of the logits
    argmax, which the fused tail reproduces tie-for-tie)."""
    model, params = served
    assert _greedy_streams(model, params, fused_head=True) == \
        _greedy_streams(model, params)


def test_engine_fused_head_int8_greedy_parity(served):
    """The full int8 fused tail: int8 codes + scales fed straight to the
    kernel equal the int8_sim oracle path (dequantized f32 weights,
    unfused tail) token-for-token on the fixture prompts."""
    model, params = served
    fused = _greedy_streams(model, params, fused_head=True,
                            weight_quant="int8")
    oracle = _greedy_streams(model, params, weight_quant="int8_sim")
    assert fused == oracle


def test_engine_fused_head_rejects_non_dense(served):
    """The capability row: fused_head composes with the dense single-
    device step only — paged layout and spec decode reject at init with
    the table's message."""
    from tpudml.serve import ServeConfig, ServingEngine
    from tpudml.serve.engine import ServeCompositionError

    model, params = served
    with pytest.raises(ServeCompositionError, match="fused_head"):
        ServingEngine(model, params, ServeConfig(
            slots=2, max_len=32, prefill_chunk=4, fused_head=True,
            cache_layout="paged", page_size=4))
    with pytest.raises(ServeCompositionError, match="fused_head"):
        ServingEngine(model, params, ServeConfig(
            slots=2, max_len=32, prefill_chunk=4, fused_head=True,
            spec_k=2))


def test_cost_model_prices_fused_tail(served):
    """DecodeCostModel drops the [B, V] logits round-trip from the
    per-slot HBM bytes when the tail is fused — fused step_seconds is
    strictly cheaper at every occupancy."""
    from tpudml.serve import ServeConfig
    from tpudml.serve.sched import DecodeCostModel, SLOConfig

    model, _ = served
    slo = SLOConfig(tpot_budget_s=0.01)
    plain = DecodeCostModel(
        model, ServeConfig(slots=2, max_len=32, prefill_chunk=4), slo)
    fused = DecodeCostModel(
        model, ServeConfig(slots=2, max_len=32, prefill_chunk=4,
                           fused_head=True), slo)
    assert fused.tail_bytes_per_slot == 0
    assert plain.tail_bytes_per_slot == 2 * V * 4
    for n in (1, 2):
        assert fused.step_seconds(n) < plain.step_seconds(n)


# ------------------------------------------------------- junction block


def _junction_operands(b=2, t=16, h=4, dh=8, seed=0):
    rng = np.random.default_rng(seed)
    d = h * dh
    f32 = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32))
    return (f32(b, t, h, dh), f32(b, t, h, dh), f32(b, t, h, dh),
            f32(b, t, d), f32(d, d) * 0.2, f32(d), f32(d), f32(d))


def _junction_loss(fn):
    def loss(q, k, v, r, wo, bo, scale, bias):
        s, y = fn(q, k, v, r, wo, bo, scale, bias)
        return jnp.sum(y * jnp.cos(s)) + jnp.sum(s * s) * 1e-2
    return loss


def test_junction_grad_parity_single_shard():
    """The representative tier-1 grad-exact case: the fused junction's
    chained kernel vjps (flash recompute-tiles → projection transpose →
    add+LN one-pass) equal the unfused reference end to end in
    interpret mode."""
    ops = _junction_operands()
    lf, gf = jax.value_and_grad(
        _junction_loss(lambda *a: fused_attn_junction(*a, interpret=True)),
        argnums=tuple(range(8)))(*ops)
    lr, gr = jax.value_and_grad(
        _junction_loss(reference_attn_junction),
        argnums=tuple(range(8)))(*ops)
    np.testing.assert_allclose(float(lf), float(lr), rtol=1e-6)
    # atol: on this XLA 1 of 1024 elements of one gradient leaf (|g| up to
    # ~10) differs by 2.1e-6 absolute at a near-zero entry (5.8e-5
    # relative) — an ulp of the leaf's scale, not of the entry.
    _assert_tree_close(gf, gr, atol=5e-6)


@pytest.mark.slow
def test_junction_grad_parity_sharded_sweep():
    """The heaviest parity sweep: the fused junction under each train
    regime's sharding — batch over data (DP), heads gathered over model
    (TP), and batch×heads with the out-projection FSDP-gathered over
    data (FSDP×TP) — gradients at single-shard tolerances against the
    unsharded reference. The junction is batch-parallel; feature-bearing
    operands follow the fused-xent compose discipline: gather on use,
    psum the data-sharded row-sum loss."""
    ops = _junction_operands(b=4, seed=7)
    lr, gr = jax.value_and_grad(
        _junction_loss(reference_attn_junction),
        argnums=tuple(range(8)))(*ops)

    def check(fn, in_specs, mesh):
        sharded = shard_map_fn(
            fn, mesh, in_specs=in_specs, out_specs=P())
        ls, gs = jax.value_and_grad(sharded, argnums=tuple(range(8)))(*ops)
        np.testing.assert_allclose(float(ls), float(lr), rtol=1e-6)
        _assert_tree_close(gs, gr)

    fused = _junction_loss(
        lambda *a: fused_attn_junction(*a, interpret=True))

    # DP: batch rows sharded, weights replicated; the loss is a SUM over
    # rows, so the shard merge is psum.
    dp = make_mesh(MeshConfig({"data": 4}), jax.devices()[:4])

    def dp_body(*a):
        return jax.lax.psum(fused(*a), "data")

    check(dp_body,
          (P("data"), P("data"), P("data"), P("data"), P(), P(), P(), P()),
          dp)

    # TP: heads sharded over model, gathered on use (causal attention
    # needs every head's full sequence; the junction consumes the
    # gathered block, per-shard loss already replicated).
    tp = make_mesh(MeshConfig({"model": 4}), jax.devices()[:4])

    def tp_body(q, k, v, *rest):
        qg = jax.lax.all_gather(q, "model", axis=2, tiled=True)
        kg = jax.lax.all_gather(k, "model", axis=2, tiled=True)
        vg = jax.lax.all_gather(v, "model", axis=2, tiled=True)
        return fused(qg, kg, vg, *rest)

    hs = P(None, None, "model")
    check(tp_body, (hs, hs, hs, P(), P(), P(), P(), P()), tp)

    # FSDP×TP: batch over data AND heads over model, wo row-sharded
    # over data and gathered on use (its transpose is the ZeRO
    # reduce-scatter for dWo), loss pmean'd over data.
    ft = make_mesh(MeshConfig({"data": 2, "model": 2}), jax.devices()[:4])

    def ft_body(q, k, v, r, wo, *rest):
        qg = jax.lax.all_gather(q, "model", axis=2, tiled=True)
        kg = jax.lax.all_gather(k, "model", axis=2, tiled=True)
        vg = jax.lax.all_gather(v, "model", axis=2, tiled=True)
        wg = jax.lax.all_gather(wo, "data", axis=0, tiled=True)
        return jax.lax.psum(fused(qg, kg, vg, r, wg, *rest), "data")

    bhs = P("data", None, "model")
    check(ft_body,
          (bhs, bhs, bhs, P("data"), P("data"), P(), P(), P()), ft)


# ------------------------------------------------ train engines × flash


def _tokens(seed=3, b=4, t=16):
    rng = np.random.default_rng(seed)
    return rng.integers(0, V, size=(b, t + 1)).astype(np.int32)


def _run_steps(engine, steps=2, seed=3):
    ts = engine.create_state(seed_key(0))
    step = engine.make_train_step()
    batch = _tokens(seed)
    losses = []
    for _ in range(steps):
        ts, m = step(ts, batch[:, :-1], batch[:, 1:])
        losses.append(float(m["loss"]))
    return ts, losses


def test_dp_flash_attn_matches_unfused():
    from tpudml.parallel.dp import DataParallel

    mesh = make_mesh(MeshConfig({"data": 4}), jax.devices()[:4])
    model = _model(max_len=16)
    common = dict(stacked_batches=False)
    ts_f, loss_f = _run_steps(
        DataParallel(model, make_optimizer("sgd", 0.05), mesh,
                     flash_attn=True, **common))
    ts_u, loss_u = _run_steps(
        DataParallel(model, make_optimizer("sgd", 0.05), mesh, **common))
    np.testing.assert_allclose(loss_f, loss_u, rtol=1e-5)
    _assert_tree_close(ts_f.params, ts_u.params)


def test_tp_and_fsdp_flash_attn_match_unfused():
    from tpudml.parallel.fsdp import FSDP
    from tpudml.parallel.mp import GSPMDParallel, tensor_parallel_rules

    mesh = make_mesh(MeshConfig({"model": 4}), jax.devices()[:4])
    model = _model(max_len=16)

    def tp_eng(flash):
        return GSPMDParallel(
            model, make_optimizer("sgd", 0.05), mesh,
            rule=tensor_parallel_rules("model"), axis_name="model",
            flash_attn=flash)

    ts_f, loss_f = _run_steps(tp_eng(True))
    ts_u, loss_u = _run_steps(tp_eng(False))
    np.testing.assert_allclose(loss_f, loss_u, rtol=1e-5)
    _assert_tree_close(ts_f.params, ts_u.params)

    fmesh = make_mesh(MeshConfig({"data": 4}), jax.devices()[:4])

    def fs_eng(flash):
        return FSDP(model, make_optimizer("sgd", 0.05), fmesh,
                    flash_attn=flash)

    ts_f, loss_f = _run_steps(fs_eng(True))
    ts_u, loss_u = _run_steps(fs_eng(False))
    np.testing.assert_allclose(loss_f, loss_u, rtol=1e-5)
    _assert_tree_close(ts_f.params, ts_u.params)


def test_flash_attn_rejects_non_dense_trunks():
    """The capability row: flash_attn swaps the DENSE causal trunk only
    — ring/ulysses trunks (already sequence-fused) and seq_sharded
    models reject at construction with the table's key."""
    from tpudml.capabilities import CompositionError
    from tpudml.parallel.dp import DataParallel

    mesh = make_mesh(MeshConfig({"data": 2}), jax.devices()[:2])
    opt = make_optimizer("sgd", 0.05)
    with pytest.raises(CompositionError, match="flash_attn"):
        DataParallel(_model(max_len=16, impl="ring", seq_sharded=True),
                     opt, mesh, flash_attn=True)


# --------------------------------------------------- TP overlap matmul


def test_tp_overlap_matmul_value_and_grad_parity():
    """Chunked psum-overlapped matmul == unchunked psum(x @ w) in value
    and gradient under TP and FSDP×TP meshes (the chunk split is over
    rows the reduce never mixes)."""
    from tpudml.parallel.overlap import tp_overlap_matmul

    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(8, 16)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32))

    def run(mesh, body, in_specs):
        fn = shard_map_fn(body, mesh, in_specs=in_specs, out_specs=P())
        loss = lambda x, w: jnp.sum(jnp.sin(fn(x, w)))
        return jax.value_and_grad(loss, argnums=(0, 1))(x, w)

    tp = make_mesh(MeshConfig({"model": 4}), jax.devices()[:4])
    specs = (P(), P(None, "model"))

    lo, go = run(tp, lambda x, w: tp_overlap_matmul(
        x, w, axis_name="model"), specs)
    lr, gr = run(tp, lambda x, w: jax.lax.psum(
        jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype),
        "model"), specs)
    np.testing.assert_allclose(float(lo), float(lr), rtol=1e-6)
    # atol: on this XLA 2 of 128 dx elements differ by up to 4.2e-6
    # absolute at near-zero entries (2.1e-4 relative) — the chunked and
    # the whole contraction sum in different orders.
    _assert_tree_close(go, gr, atol=5e-6)

    ft = make_mesh(MeshConfig({"data": 2, "model": 2}), jax.devices()[:4])
    ft_specs = (P("data"), P(None, "model"))
    lo, go = run(ft, lambda x, w: tp_overlap_matmul(
        x, w, axis_name="model", chunks=2), ft_specs)
    lr, gr = run(ft, lambda x, w: jax.lax.psum(
        jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype),
        "model"), ft_specs)
    np.testing.assert_allclose(float(lo), float(lr), rtol=1e-6)
    _assert_tree_close(go, gr)


def test_tp_overlap_rejects_trivial_axis():
    from tpudml.capabilities import CompositionError
    from tpudml.parallel.overlap import tp_overlap_matmul

    mesh = make_mesh(MeshConfig({"model": 1}), jax.devices()[:1])
    x = jnp.ones((4, 8), jnp.float32)
    w = jnp.ones((8, 4), jnp.float32)
    body = shard_map_fn(
        lambda x, w: tp_overlap_matmul(x, w, axis_name="model"),
        mesh, in_specs=(P(), P()), out_specs=P())
    with pytest.raises(CompositionError, match="tp_overlap"):
        body(x, w)


def test_planner_enumerates_and_prices_overlap():
    """plan/space enumerates tp_overlap TP candidates and plan/score
    prices them with the exposed-vs-hidden split: overlap moves exactly
    (K−1)/K of the TP wire from exposed to hidden, total wire equal."""
    import dataclasses

    from tpudml.parallel.overlap import OVERLAP_CHUNKS
    from tpudml.plan.score import score_candidate
    from tpudml.plan.space import enumerate_candidates, flagship_lm

    cands = [c for c in enumerate_candidates(4, engines=("tp",))
             if c.tp_overlap]
    assert cands, "no overlap TP candidate enumerated"
    cand = cands[0]
    spec = flagship_lm()
    on = score_candidate(spec, cand)
    off = score_candidate(spec, dataclasses.replace(cand, tp_overlap=False))
    moved = off.exposed_comm_s - on.exposed_comm_s
    assert moved > 0
    # every second moved off the exposed term lands in the hidden term
    np.testing.assert_allclose(
        on.hidden_comm_s - off.hidden_comm_s, moved, rtol=1e-9)
    # and the split is exactly (K-1)/K of the overlap-eligible TP wire:
    # exposed kept 1/K, so moved = (K-1)/K * tp_wire.
    tp_wire_s = moved * OVERLAP_CHUNKS / (OVERLAP_CHUNKS - 1)
    np.testing.assert_allclose(
        on.exposed_comm_s - (off.exposed_comm_s - tp_wire_s),
        tp_wire_s / OVERLAP_CHUNKS, rtol=1e-9)
    assert on.comm_wire_bytes == off.comm_wire_bytes
