"""Round-4 fused kernels composed with the parallel engines.

Load-bearing properties (VERDICT r4 item 1):

- ``fused_xent`` on the DP/CP engines trains the SAME trajectory as the
  unfused logits path — the fused head loss fn is token-parallel, so per-
  shard token means pmean to the global mean under any batch/sequence
  sharding (equal shards);
- ``fused_ln`` threads through the CP trunk (TransformerLM) and the
  pipeline stage (TransformerBlock's ln2-junction fusion) with identical
  math to the unfused junctions;
- ``fused_ln`` + MoE is the same function as the unfused MoE trunk
  (the junction kernel fuses the residual ADD, not the FFN branch; aux
  state threads through the deferred trunk); save_scores without
  fused_xent raises at engine construction.

On CPU both kernels dispatch to reference math, so these tests pin the
PLUMBING and the sharded-mean structure; kernel numerics are pinned
separately in interpret mode (test_layernorm_kernel / test_xent_kernel).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudml.core.config import MeshConfig
from tpudml.core.dist import make_mesh
from tpudml.core.prng import seed_key
from tpudml.models import TransformerBlock, TransformerLM
from tpudml.optim import make_optimizer
from tpudml.parallel.cp import ContextParallel
from tpudml.parallel.dp import DataParallel

V, B, T, DIM, HEADS, LAYERS = 32, 4, 16, 16, 4, 2


def _tokens(seed=3, t=T, b=B):
    rng = np.random.default_rng(seed)
    return rng.integers(0, V, size=(b, t + 1)).astype(np.int32)


def _lm(**kw):
    cfg = dict(
        vocab_size=V, embed_dim=DIM, num_heads=HEADS, num_layers=LAYERS,
        max_len=T,
    )
    cfg.update(kw)
    return TransformerLM(**cfg)


def _run_steps(engine, steps=2, seed=3):
    ts = engine.create_state(seed_key(0))
    step = engine.make_train_step()
    batch = _tokens(seed)
    losses = []
    for _ in range(steps):
        ts, m = step(ts, batch[:, :-1], batch[:, 1:])
        losses.append(float(m["loss"]))
    return ts, losses


def _assert_tree_close(a, b, rtol=1e-5, atol=1e-6):
    flat_a = jax.tree_util.tree_leaves_with_path(a)
    flat_b = dict(jax.tree_util.tree_leaves_with_path(b))
    for path, la in flat_a:
        np.testing.assert_allclose(
            np.asarray(la), np.asarray(flat_b[path]), rtol=rtol, atol=atol,
            err_msg=jax.tree_util.keystr(path),
        )


# ------------------------------------------------------------ CP × fused


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_cp_fused_xent_matches_unfused(impl):
    mesh = make_mesh(MeshConfig({"seq": 4}), jax.devices()[:4])
    opt = make_optimizer("sgd", 0.05)
    model = _lm(impl=impl, seq_sharded=True)
    ts_f, loss_f = _run_steps(
        ContextParallel(model, opt, mesh, fused_xent=True)
    )
    ts_u, loss_u = _run_steps(ContextParallel(model, opt, mesh))
    np.testing.assert_allclose(loss_f, loss_u, rtol=1e-5)
    _assert_tree_close(ts_f.params, ts_u.params)


def test_cp_fused_ln_matches_unfused():
    mesh = make_mesh(MeshConfig({"seq": 4}), jax.devices()[:4])
    opt = make_optimizer("sgd", 0.05)
    ts_f, loss_f = _run_steps(
        ContextParallel(
            _lm(impl="ring", seq_sharded=True, fused_ln=True), opt, mesh
        )
    )
    ts_u, loss_u = _run_steps(
        ContextParallel(_lm(impl="ring", seq_sharded=True), opt, mesh)
    )
    np.testing.assert_allclose(loss_f, loss_u, rtol=1e-5)
    _assert_tree_close(ts_f.params, ts_u.params)


def test_cp_fused_ln_and_xent_together_match_single_device():
    """The full round-4 step — fused trunk + fused head — under the seq
    sharding tracks the single-device unfused trajectory."""
    from tpudml.train import TrainState, make_train_step

    mesh = make_mesh(MeshConfig({"seq": 4}), jax.devices()[:4])
    # SGD for trajectory parity: parameters with a ~zero true gradient
    # (e.g. the attention k bias, shift-invariant under softmax) carry
    # pure float noise — Adam normalizes that noise to O(1) sign-flip
    # updates, which would fail ANY two numerically-different-but-equal
    # implementations. SGD keeps noise at noise scale.
    opt = make_optimizer("sgd", 0.05)
    cp = ContextParallel(
        _lm(impl="ring", seq_sharded=True, fused_ln=True), opt, mesh,
        fused_xent=True,
    )
    ts_f, loss_f = _run_steps(cp)

    single = _lm(impl="full")
    ts = TrainState.create(single, opt, seed_key(0))
    step = make_train_step(single, opt)
    batch = _tokens()
    losses = []
    for _ in range(2):
        ts, m = step(ts, batch[:, :-1], batch[:, 1:])
        losses.append(float(m["loss"]))
    np.testing.assert_allclose(loss_f, losses, rtol=1e-4)
    _assert_tree_close(ts_f.params, ts.params, rtol=1e-4, atol=1e-5)


def test_cp_striped_fused_xent_matches_unfused():
    mesh = make_mesh(MeshConfig({"seq": 4}), jax.devices()[:4])
    opt = make_optimizer("sgd", 0.05)
    model = _lm(impl="ring", seq_sharded=True, seq_layout="striped")
    ts_f, loss_f = _run_steps(
        ContextParallel(model, opt, mesh, layout="striped", fused_xent=True)
    )
    ts_u, loss_u = _run_steps(
        ContextParallel(model, opt, mesh, layout="striped")
    )
    np.testing.assert_allclose(loss_f, loss_u, rtol=1e-5)
    _assert_tree_close(ts_f.params, ts_u.params)


# ------------------------------------------------------------ DP × fused


def test_dp_fused_xent_matches_unfused():
    mesh = make_mesh(MeshConfig({"data": 4}), jax.devices()[:4])
    opt = make_optimizer("sgd", 0.05)
    model = _lm(impl="full")
    common = dict(stacked_batches=False)
    ts_f, loss_f = _run_steps(
        DataParallel(model, opt, mesh, fused_xent=True, **common)
    )
    ts_u, loss_u = _run_steps(DataParallel(model, opt, mesh, **common))
    np.testing.assert_allclose(loss_f, loss_u, rtol=1e-5)
    _assert_tree_close(ts_f.params, ts_u.params)


@pytest.mark.slow
def test_dp_fused_xent_with_accum_matches_plain():
    """fused_xent × accum_steps (previously rejected at construction):
    the fused loss threads through the micro-batch scan with grad-exact
    parity — mean of equal-chunk token means == batch token mean."""
    mesh = make_mesh(MeshConfig({"data": 2}), jax.devices()[:2])
    model = _lm(impl="full")
    common = dict(stacked_batches=False, fused_xent=True)
    ts_a, loss_a = _run_steps(
        DataParallel(
            model, make_optimizer("sgd", 0.05), mesh, accum_steps=2, **common
        )
    )
    ts_1, loss_1 = _run_steps(
        DataParallel(model, make_optimizer("sgd", 0.05), mesh, **common)
    )
    np.testing.assert_allclose(loss_a, loss_1, rtol=1e-5)
    _assert_tree_close(ts_a.params, ts_1.params)


# ------------------------------------------ sharded head (TP/FSDP) × fused


def _tp_rules():
    from tpudml.parallel.mp import tensor_parallel_rules

    return tensor_parallel_rules("model")


def test_tp_fused_xent_matches_unfused():
    """Vocab-sharded fused head under tensor parallelism: per-shard
    partial (lse, picked) statistics merged by the online lse rule train
    the SAME trajectory as the unfused sharded logits path."""
    from tpudml.parallel.mp import GSPMDParallel

    mesh = make_mesh(MeshConfig({"model": 4}), jax.devices()[:4])
    model = _lm(impl="full")

    def eng(fused):
        return GSPMDParallel(
            model, make_optimizer("sgd", 0.05), mesh, rule=_tp_rules(),
            axis_name="model", fused_xent=fused,
        )

    ts_f, loss_f = _run_steps(eng(True))
    ts_u, loss_u = _run_steps(eng(False))
    np.testing.assert_allclose(loss_f, loss_u, rtol=1e-5)
    _assert_tree_close(ts_f.params, ts_u.params)


def test_fsdp_fused_xent_matches_unfused():
    """1-D FSDP shards tokens AND vocab over the same axis; the fused
    path all-gathers tokens into the head region so each shard scores
    all tokens against its vocab slice — grad-exact vs unfused FSDP."""
    from tpudml.parallel.fsdp import FSDP

    mesh = make_mesh(MeshConfig({"data": 4}), jax.devices()[:4])
    model = _lm(impl="full")

    def eng(fused):
        return FSDP(model, make_optimizer("sgd", 0.05), mesh, fused_xent=fused)

    ts_f, loss_f = _run_steps(eng(True))
    ts_u, loss_u = _run_steps(eng(False))
    np.testing.assert_allclose(loss_f, loss_u, rtol=1e-5)
    _assert_tree_close(ts_f.params, ts_u.params)


def test_fsdp_tp_fused_xent_matches_unfused():
    """2-D FSDP×TP composition: head kernel P('data', 'model') — vocab
    merge over model, W all-gathered over data on use (its transpose IS
    the ZeRO reduce-scatter for dW), tokens stay data-sharded with a
    final pmean. Grad-exact vs the unfused 2-D engine."""
    from tpudml.parallel.fsdp import FSDP

    mesh = make_mesh(MeshConfig({"data": 2, "model": 2}), jax.devices()[:4])
    model = _lm(impl="full")

    def eng(fused):
        return FSDP(
            model, make_optimizer("sgd", 0.05), mesh,
            base_rule=_tp_rules(), fused_xent=fused,
        )

    ts_f, loss_f = _run_steps(eng(True))
    ts_u, loss_u = _run_steps(eng(False))
    np.testing.assert_allclose(loss_f, loss_u, rtol=1e-5)
    _assert_tree_close(ts_f.params, ts_u.params)


def test_tp_fused_xent_indivisible_vocab_falls_back():
    """A vocab the mesh can't divide demotes the head spec to replicated
    — the sharded loss fn then takes the plain full-vocab kernel path
    inside the shard_map region, still matching the unfused engine."""
    from tpudml.parallel.mp import GSPMDParallel

    mesh = make_mesh(MeshConfig({"model": 4}), jax.devices()[:4])
    model = _lm(impl="full", vocab_size=34)  # 34 % 4 != 0 -> demoted

    def eng(fused):
        return GSPMDParallel(
            model, make_optimizer("sgd", 0.05), mesh, rule=_tp_rules(),
            axis_name="model", fused_xent=fused,
        )

    ts_f, loss_f = _run_steps(eng(True))
    ts_u, loss_u = _run_steps(eng(False))
    np.testing.assert_allclose(loss_f, loss_u, rtol=1e-5)
    _assert_tree_close(ts_f.params, ts_u.params)


@pytest.mark.parametrize("save_s", [False, True])
def test_sharded_kernel_path_grad_parity(save_s):
    """The Pallas machinery itself (interpret mode) under every sharded
    composition: value AND gradients match the unsharded reference at
    the single-shard parity tolerances. The engine tests above exercise
    the reference dispatch on CPU; this pins the kernel dispatch —
    including the shard_map transpose convention the custom_vjp's
    cotangent psum compensates for."""
    from jax.sharding import PartitionSpec as P

    from tpudml.ops.xent_kernel import (
        linear_cross_entropy,
        sharded_linear_cross_entropy,
    )
    from tpudml.parallel.sharding import shard_map_fn

    n, d, v = 16, 8, 64
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(d, v)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(v,)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, v, size=(n,)).astype(np.int32))
    labels = labels.at[3].set(v + 5)  # out-of-range: loss = lse row

    lr, gr = jax.value_and_grad(
        lambda x, w, b: linear_cross_entropy(x, w, labels, b),
        argnums=(0, 1, 2),
    )(x, w, b)

    def check(fn):
        ls, gs = jax.value_and_grad(fn, argnums=(0, 1, 2))(x, w, b)
        np.testing.assert_allclose(float(ls), float(lr), rtol=1e-6)
        for got, want, nm in zip(gs, gr, ("dx", "dw", "db")):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6,
                err_msg=nm,
            )

    # TP: x replicated, vocab sharded over "model".
    tp = make_mesh(MeshConfig({"model": 4}), jax.devices()[:4])

    def tp_loss(x, w, b):
        def body(x, w, b, ln):
            return sharded_linear_cross_entropy(
                x, w, ln, b, axis_name="model", interpret=True,
                save_s=save_s,
            )
        return shard_map_fn(
            body, tp,
            in_specs=(P(), P(None, "model"), P("model"), P()),
            out_specs=P(),
        )(x, w, b, labels)

    check(tp_loss)

    # 1-D FSDP: tokens AND vocab share "data"; gather the batch first.
    fs = make_mesh(MeshConfig({"data": 4}), jax.devices()[:4])

    def fs_loss(x, w, b):
        def body(x, w, b, ln):
            xg = jax.lax.all_gather(x, "data", axis=0, tiled=True)
            lg = jax.lax.all_gather(ln, "data", axis=0, tiled=True)
            return sharded_linear_cross_entropy(
                xg, w, lg, b, axis_name="data", interpret=True,
                save_s=save_s,
            )
        return shard_map_fn(
            body, fs,
            in_specs=(P("data"), P(None, "data"), P("data"), P("data")),
            out_specs=P(),
        )(x, w, b, labels)

    check(fs_loss)

    # 2-D FSDP×TP: tokens over "data", vocab over "model", W dim 0
    # gathered over "data" on use, per-shard token means pmean'd.
    ft = make_mesh(MeshConfig({"data": 2, "model": 2}), jax.devices()[:4])

    def ft_loss(x, w, b):
        def body(x, w, b, ln):
            k = jax.lax.all_gather(w, "data", axis=0, tiled=True)
            loss = sharded_linear_cross_entropy(
                x, k, ln, b, axis_name="model", interpret=True,
                save_s=save_s,
            )
            return jax.lax.pmean(loss, "data")
        return shard_map_fn(
            body, ft,
            in_specs=(P("data"), P("data", "model"), P("model"), P("data")),
            out_specs=P(),
        )(x, w, b, labels)

    check(ft_loss)


# ------------------------------------------------------- pipeline × fused


def test_block_fused_ln_grads_match_unfused():
    """The ln2-junction fusion is the same function as the unfused block —
    values and gradients."""
    block_u = TransformerBlock(DIM, HEADS)
    block_f = TransformerBlock(DIM, HEADS, fused_ln=True)
    params, _ = block_u.init(seed_key(1))
    x = jnp.asarray(
        np.random.default_rng(5).normal(size=(B, T, DIM)).astype(np.float32)
    )

    def loss(block, p):
        out, _ = block.apply(p, {}, x)
        return jnp.sum(out * jnp.cos(x))  # fixed nontrivial cotangent

    lu, gu = jax.value_and_grad(lambda p: loss(block_u, p))(params)
    lf, gf = jax.value_and_grad(lambda p: loss(block_f, p))(params)
    np.testing.assert_allclose(float(lf), float(lu), rtol=1e-6)
    _assert_tree_close(gf, gu)


def test_pp_fused_ln_matches_unfused():
    from tpudml.models import TransformerEmbed, TransformerHead
    from tpudml.parallel.pp import GPipe

    mesh = make_mesh(MeshConfig({"stage": 4}), jax.devices()[:4])
    opt = make_optimizer("sgd", 0.05)

    def pipe(fused):
        return GPipe(
            TransformerBlock(DIM, HEADS, fused_ln=fused),
            n_microbatches=2,
            mesh=mesh,
            optimizer=opt,
            prologue=TransformerEmbed(V, DIM, T),
            epilogue=TransformerHead(DIM, V),
        )

    ts_f, loss_f = _run_steps(pipe(True))
    ts_u, loss_u = _run_steps(pipe(False))
    np.testing.assert_allclose(loss_f, loss_u, rtol=1e-5)
    _assert_tree_close(ts_f.params, ts_u.params)


def test_task5_accepts_fused_flags_multichip():
    """task5 runs --fused_xent/--fused_ln under cp/dp/pp end-to-end."""
    from tasks.task5_longcontext import main

    base = ["--steps", "2", "--seq_len", "16", "--batch_size", "4",
            "--vocab", "32", "--embed_dim", "16", "--num_heads", "4",
            "--num_layers", "1", "--log_every", "0", "--n_devices", "2"]
    out = main(base + ["--parallel", "cp", "--fused_xent", "--fused_ln"])
    assert np.isfinite(out["final_loss"])
    out = main(base + ["--parallel", "dp", "--fused_xent"])
    assert np.isfinite(out["final_loss"])
    out = main(base + ["--parallel", "pp", "--fused_ln",
                       "--microbatches", "2"])
    assert np.isfinite(out["final_loss"])
    out = main(base + ["--parallel", "tp", "--fused_xent"])
    assert np.isfinite(out["final_loss"])
    out = main(base + ["--parallel", "fsdp", "--fused_xent"])
    assert np.isfinite(out["final_loss"])


# ------------------------------------------------------------------ guards


def test_fused_ln_moe_matches_unfused():
    """fused_ln composes with MoE: the deferred trunk routes the FFN
    branch through the MoE layer and threads the aux-loss state, so
    values, gradients (router included), AND the aux loss match the
    unfused MoE trunk."""
    kw = dict(moe_experts=2, moe_capacity_factor=8.0)
    lm_u = _lm(**kw)
    lm_f = _lm(fused_ln=True, **kw)
    params, state = lm_u.init(seed_key(2))
    toks = jnp.asarray(_tokens()[:, :-1])

    def loss(lm, p):
        logits, new_state = lm.apply(p, state, toks, train=True)
        from tpudml.train import collect_aux_losses
        return jnp.sum(jnp.sin(logits)) * 1e-2 + \
            jnp.sum(logits**2) * 1e-3 + collect_aux_losses(new_state)

    lu, gu = jax.value_and_grad(lambda p: loss(lm_u, p))(params)
    lf, gf = jax.value_and_grad(lambda p: loss(lm_f, p))(params)
    np.testing.assert_allclose(float(lf), float(lu), rtol=1e-5)
    _assert_tree_close(gf, gu)

    # The pipeline-stage form too (block-level ln2 fusion + MoE).
    block_u = TransformerBlock(DIM, HEADS, **kw)
    block_f = TransformerBlock(DIM, HEADS, fused_ln=True, **kw)
    bp, bs = block_u.init(seed_key(3))
    x = jnp.asarray(
        np.random.default_rng(5).normal(size=(B, T, DIM)).astype(np.float32)
    )

    def bloss(block, p):
        out, st = block.apply(p, bs, x)
        return jnp.sum(out * jnp.cos(x)) + st["moe"]["aux_loss"]

    blu, bgu = jax.value_and_grad(lambda p: bloss(block_u, p))(bp)
    blf, bgf = jax.value_and_grad(lambda p: bloss(block_f, p))(bp)
    np.testing.assert_allclose(float(blf), float(blu), rtol=1e-6)
    _assert_tree_close(bgf, bgu)


def test_fused_ln_moe_matches_unfused_under_ep():
    """fused_ln + MoE under expert parallelism: the shard_map EP engine
    trains the SAME trajectory fused vs unfused — the junction kernel
    fuses the residual add, not the FFN branch, so expert dispatch across
    the mesh and the psum'd aux loss are untouched (README's 'including
    under expert parallelism' claim, pinned on the CPU mesh)."""
    from tpudml.parallel.ep import ExpertParallel

    mesh = make_mesh(MeshConfig({"expert": 2}), jax.devices()[:2])
    kw = dict(moe_experts=2, moe_capacity_factor=8.0, moe_axis="expert")
    opt = lambda: make_optimizer("adam", 1e-2)
    ts_u, loss_u = _run_steps(ExpertParallel(_lm(**kw), opt(), mesh))
    ts_f, loss_f = _run_steps(
        ExpertParallel(_lm(fused_ln=True, **kw), opt(), mesh)
    )
    np.testing.assert_allclose(loss_f, loss_u, rtol=1e-5)
    _assert_tree_close(ts_f.params, ts_u.params)


def test_save_scores_requires_fused_xent():
    mesh = make_mesh(MeshConfig({"data": 2}), jax.devices()[:2])
    opt = make_optimizer("adam", 1e-3)
    with pytest.raises(ValueError, match="save_scores"):
        DataParallel(_lm(), opt, mesh, save_scores=True)
    seq = make_mesh(MeshConfig({"seq": 2}), jax.devices()[:2])
    with pytest.raises(ValueError, match="save_scores"):
        ContextParallel(
            _lm(impl="ring", seq_sharded=True), opt, seq, save_scores=True
        )


def test_task5_fused_xent_rejects_pp_only():
    """pp is the one remaining non-composition: the pipeline epilogue
    ships logits between stages, so there is no feature tensor for the
    fused head to consume. tp/fsdp now build (covered above)."""
    from tasks.task5_longcontext import build_engine, parse_args

    args = parse_args(["--parallel", "pp", "--fused_xent"])
    with pytest.raises(ValueError, match="fused_xent"):
        build_engine(args, jax.devices()[:2])


# ---------------------------------------------- embed backward chunking


def test_embed_backward_chunked_matches_dense(monkeypatch):
    """Above the one-hot cap the scan-chunked dTable equals the dense
    matmul (and autodiff-of-gather)."""
    from tpudml.models import transformer as tr

    table = jnp.asarray(
        np.random.default_rng(7).normal(size=(V, DIM)).astype(np.float32)
    )
    tokens = jnp.asarray(_tokens(11)[:, :T])
    cot = jnp.asarray(
        np.random.default_rng(8).normal(
            size=(*tokens.shape, DIM)
        ).astype(np.float32)
    )

    def grad_of(fn):
        return jax.grad(lambda t: jnp.sum(fn(t, tokens) * cot))(table)

    dense = grad_of(tr.embed_lookup)
    # n*V = 64*32 = 2048; a cap of 256 forces chunking (chunk=8 rows).
    monkeypatch.setattr(tr, "_ONEHOT_ELEM_CAP", 256)
    chunked = grad_of(tr.embed_lookup)
    reference = grad_of(lambda tab, tok: tab[tok])
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(dense), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(reference), rtol=1e-5, atol=1e-6)


def test_save_s_auto_threshold():
    """save_s=None resolves to speed mode iff the padded f32 score
    residual fits SAVE_S_AUTO_MAX_BYTES (VERDICT r4 item 5's default-on
    criterion): flagship 8k×32k (1 GiB) and chip-filling 16k×32k (2 GiB)
    are ON; the 131k-token long-context regime (16 GiB) falls back to
    the O(N) lean contract."""
    from tpudml.ops.xent_kernel import _auto_save_s

    bn, bv = 256, 2048
    auto = lambda n, v: _auto_save_s(n, 512, v, jnp.bfloat16, jnp.bfloat16,
                                     bn, bv)
    assert auto(8192, 32768) is True     # flagship
    assert auto(16384, 32768) is True    # --large (2 GiB)
    assert auto(16640, 32768) is False   # just past budget
    assert auto(131072, 32768) is False  # long-context
    # Padding counts: n=1 still pads to a block row multiple of 8.
    assert auto(1, 256) is True


def test_save_s_auto_threshold_sharded(monkeypatch):
    """The sharded head resolves save_s=None against its LOCAL vocab —
    each shard holds a 1/W slice of the score residual, so a 16k×32k
    problem that is lean unsharded (2 GiB + one padded block row) flips
    to speed mode once 4 shards each hold 16k×8k (512 MiB). Pinned at
    the exact byte boundary, and the wiring is pinned by recording the
    (n, v) the public entry point hands to the auto rule."""
    from jax.sharding import PartitionSpec as P

    from tpudml.ops import xent_kernel as xk
    from tpudml.parallel.sharding import shard_map_fn

    bn, bv = 256, 2048
    n, v, shards = 16640, 32768, 4
    bf16 = jnp.bfloat16
    # Unsharded: one padded block row past the 2 GiB budget.
    assert xk._auto_save_s(n, 512, v, bf16, bf16, bn, bv) is False
    _, _, n_pad, v_pad = xk._padded_dims(n, v, bn, bv)
    assert (n_pad - bn) * v_pad * 4 == xk.SAVE_S_AUTO_MAX_BYTES
    # Each shard's residual is exactly 1/W of that -> back under budget.
    assert xk._auto_save_s(n, 512, v // shards, bf16, bf16, bn, bv) is True

    # And sharded_linear_cross_entropy really uses the local slice.
    seen = []
    real = xk._auto_save_s

    def spy(n, d, v, *rest):
        seen.append((n, v))
        return real(n, d, v, *rest)

    monkeypatch.setattr(xk, "_auto_save_s", spy)
    mesh = make_mesh(MeshConfig({"model": 4}), jax.devices()[:4])
    nn, d, vv = 8, 4, 32
    x = jnp.zeros((nn, d), jnp.float32)
    w = jnp.zeros((d, vv), jnp.float32)
    labels = jnp.zeros((nn,), jnp.int32)

    def body(x, w, ln):
        return xk.sharded_linear_cross_entropy(
            x, w, ln, axis_name="model", save_s=None
        )

    shard_map_fn(
        body, mesh,
        in_specs=(P(), P(None, "model"), P()), out_specs=P(),
    )(x, w, labels)
    assert (nn, vv // 4) in seen
