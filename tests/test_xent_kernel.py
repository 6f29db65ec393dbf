"""Fused linear-cross-entropy kernel (tpudml/ops/xent_kernel.py).

Parity oracle: the XLA reference loss over materialized logits. The
Pallas kernels run under the interpreter on CPU (as in test_flash);
compiled-kernel parity on the real chip was verified at
[8192, 512] @ [512, 32768] bf16 (loss diff 4e-6, grad diff <4e-6 — see
BASELINE.md round-3 notes).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudml.nn.losses import softmax_cross_entropy
from tpudml.ops.xent_kernel import linear_cross_entropy


def ref(x, w, y, b=None):
    logits = x @ w
    if b is not None:
        logits = logits + b
    return softmax_cross_entropy(logits.astype(jnp.float32), y)


@pytest.mark.parametrize(
    "n,d,v,bn,bv",
    [
        (16, 32, 64, 8, 64),
        (24, 16, 100, 8, 128),  # vocab padded to the tile multiple
        (8, 8, 16, 16, 128),    # blocks capped at the padded sizes
    ],
)
@pytest.mark.parametrize("bias", [False, True])
@pytest.mark.parametrize("save_s", [False, True])
def test_matches_reference_loss_and_grads(n, d, v, bn, bv, bias, save_s):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (n, d), jnp.float32)
    w = jax.random.normal(key, (d, v), jnp.float32) * 0.1
    b = jax.random.normal(key, (v,), jnp.float32) * 0.1 if bias else None
    y = jax.random.randint(key, (n,), 0, v)

    fused = lambda x, w, b: linear_cross_entropy(
        x, w, y, b, block_n=bn, block_v=bv, interpret=True, save_s=save_s
    )
    np.testing.assert_allclose(
        float(fused(x, w, b)), float(ref(x, w, y, b)), rtol=1e-6, atol=1e-6
    )
    argnums = (0, 1, 2) if bias else (0, 1)
    got = jax.grad(fused, argnums=argnums)(x, w, b)
    want = jax.grad(lambda x, w, b: ref(x, w, y, b), argnums=argnums)(x, w, b)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-5, atol=1e-6)


def test_batched_shape_flattening_and_fallback():
    """[..., d] inputs flatten; non-TPU default dispatch = XLA reference."""
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (2, 8, 16), jnp.float32)
    w = jax.random.normal(key, (16, 32), jnp.float32) * 0.1
    y = jax.random.randint(key, (2, 8), 0, 32)
    got = linear_cross_entropy(x, w, y)  # CPU → XLA fallback path
    want = ref(x.reshape(-1, 16), w, y.reshape(-1))
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    with pytest.raises(ValueError, match="labels"):
        linear_cross_entropy(x, w, y[:, :4])


def test_fused_lm_train_step_learns():
    """make_lm_fused_train_step on a tiny LM: loss decreases and the step
    contract (donated TrainState, loss-only metrics) holds."""
    from tpudml.core.prng import seed_key
    from tpudml.data.datasets import synthetic_lm
    from tpudml.models import TransformerLM
    from tpudml.optim import make_optimizer
    from tpudml.train import TrainState, make_lm_fused_train_step

    model = TransformerLM(vocab_size=32, embed_dim=32, num_heads=4,
                          num_layers=1, max_len=32)
    opt = make_optimizer("adam", 1e-2)
    step = make_lm_fused_train_step(model, opt)
    ts = TrainState.create(model, opt, seed_key(0))
    seqs = jnp.asarray(synthetic_lm(8, 32, 32, seed=0))
    x, y = seqs[:, :-1], seqs[:, 1:]
    losses = []
    for _ in range(40):
        ts, m = step(ts, x, y)
        losses.append(float(m["loss"]))
    assert losses[-1] < 0.5 < losses[0]
    assert int(ts.step) == 40


def test_save_s_out_of_range_labels_and_padded_rows():
    """The save-s backward must keep the padded-row/-column semantics of
    the lean backward: zero dlogits on padded rows (lse re-padded +inf),
    no pull-up for labels landing in [V, V_pad)."""
    key = jax.random.PRNGKey(3)
    n, d, v = 10, 16, 100  # rows pad to 16, vocab pads to 128
    x = jax.random.normal(key, (n, d), jnp.float32)
    w = jax.random.normal(key, (d, v), jnp.float32) * 0.1
    y = jnp.array([0, 5, 99, 100, 110, 127, 3000, -7, 1, 2], jnp.int32)
    args = dict(block_n=16, block_v=128, interpret=True)
    loss_s = linear_cross_entropy(x, w, y, save_s=True, **args)
    loss_l = linear_cross_entropy(x, w, y, save_s=False, **args)
    np.testing.assert_allclose(float(loss_s), float(loss_l), rtol=1e-6)
    for i in (0, 1):
        gs = jax.grad(
            lambda x, w: linear_cross_entropy(x, w, y, save_s=True, **args),
            argnums=i,
        )(x, w)
        gl = jax.grad(
            lambda x, w: linear_cross_entropy(x, w, y, save_s=False, **args),
            argnums=i,
        )(x, w)
        assert np.all(np.isfinite(np.asarray(gs)))
        np.testing.assert_allclose(
            np.asarray(gs), np.asarray(gl), rtol=1e-6, atol=1e-7
        )


def test_out_of_range_labels_give_lse_loss_not_inf():
    """Labels in [V, V_pad) land on PADDED columns; the pick must exclude
    them (loss = lse, no pull-up, same as any out-of-range id) instead of
    picking the padded column's -inf (which would poison the loss)."""
    key = jax.random.PRNGKey(2)
    n, d, v = 8, 16, 100  # v pads to 128
    x = jax.random.normal(key, (n, d), jnp.float32)
    w = jax.random.normal(key, (d, v), jnp.float32) * 0.1
    y = jnp.array([0, 5, 99, 100, 110, 127, 3000, -7], jnp.int32)
    loss = linear_cross_entropy(x, w, y, block_n=8, block_v=128, interpret=True)
    assert np.isfinite(float(loss))
    g = jax.grad(
        lambda x: linear_cross_entropy(x, w, y, block_n=8, block_v=128,
                                       interpret=True)
    )(x)
    assert np.all(np.isfinite(np.asarray(g)))
    # The non-TPU fallback dispatch must implement the SAME semantics
    # (loss = lse, no pull-up for invalid ids — NOT edge-class clamping).
    fallback = linear_cross_entropy(x, w, y)  # CPU default dispatch
    np.testing.assert_allclose(float(fallback), float(loss), rtol=1e-6)


# ------------------------------------------------------------------ the plan
# v5e's published peaks (benchmarks/device.py holds the same): what a
# kernel's operand traffic and its matmuls are timed against below.
HBM_BYTES_PER_S = 819e9
BF16_FLOPS_PER_S = 197e12


def _modelled_seconds(plan, d, x_item, w_item, save_s):
    """{kernel: (seconds of HBM traffic, seconds of matmul)} of one call,
    the traffic read off the grids' index maps: a block is fetched again
    whenever its index changes between consecutive grid steps, so the
    forward and dX fetch the whole head once a row block and dW the whole
    x once a vocabulary tile; the scores move once either way."""
    n_pad, v_pad = plan.n_pad, plan.v_pad
    x_once, w_once = n_pad * d * x_item, d * v_pad * w_item
    scores = n_pad * v_pad * 4
    traffic = {
        "fwd": x_once + n_pad // plan.tile[0] * w_once
        + (scores if save_s else 0),
        "dx": n_pad // plan.tile[0] * w_once + x_once
        + (scores if save_s else x_once),
        "dw": v_pad // plan.dw[1] * x_once + w_once
        + (scores if save_s else w_once),
    }
    matmul = 2 * n_pad * d * v_pad / BF16_FLOPS_PER_S
    recompute = 1 if save_s else 2  # the lean backward recomputes s
    matmuls = {"fwd": matmul, "dx": recompute * matmul,
               "dw": recompute * matmul}
    return {k: (traffic[k] / HBM_BYTES_PER_S, matmuls[k]) for k in traffic}


@pytest.mark.parametrize("n,d,v", [
    (8192, 1024, 50257),   # gpt2-medium.pretrain-1k: 8 x 1024 tokens
    (2048, 1024, 50257),   # a data-parallel shard's rows
    (8192, 1024, 12800),   # a vocabulary shard of four
    (16384, 2048, 49152),  # starcoderbase-1b's widths
], ids=["gpt2-medium", "2k_rows", "12800_a_shard", "starcoder"])
@pytest.mark.parametrize("save_s", [True, False], ids=["save_s", "lean"])
def test_plan_streams_operands_faster_than_the_matmul(n, d, v, save_s):
    from tpudml.ops.xent_kernel import _plan

    plan = _plan(n, d, v, jnp.bfloat16, jnp.bfloat16)
    for kernel, (memory_s, matmul_s) in _modelled_seconds(
            plan, d, 2, 2, save_s).items():
        assert memory_s < matmul_s, (kernel, plan, memory_s, matmul_s)


def test_pr43_tiles_streamed_the_head_slower_than_the_matmul():
    """What the model above says of the tiles the cell ran until PR 46
    (256 rows x 2,048 columns; dW 640 wide): forward and dX memory-bound."""
    from tpudml.ops.xent_kernel import _Plan

    was = _modelled_seconds(_Plan((256, 2048), (256, 640), 8192, 51200),
                            1024, 2, 2, True)
    assert was["fwd"][0] > was["fwd"][1] and was["dx"][0] > was["dx"][1]


@pytest.mark.parametrize("n,d,v", [
    (8192, 1024, 50257), (2048, 1024, 12800), (1000, 768, 32000),
    (8192, 4096, 128256), (10, 16, 100), (24, 16, 300),
])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_plan_tiles_divide_one_padded_problem(n, d, v, dtype):
    """Whatever the shape, the three kernels tile ONE (n_pad, v_pad) in
    whole (8, 128)-aligned blocks that fit the VMEM budget in BOTH modes
    (one tiling for both is what makes them bit-identical)."""
    from tpudml.ops import xent_kernel as xk

    plan = xk._plan(n, d, v, dtype, dtype)
    assert plan.n_pad >= n and plan.v_pad >= v
    item = jnp.dtype(dtype).itemsize
    for kernel, (bn, bv) in (("fwd", plan.tile), ("dx", plan.tile),
                             ("dw", plan.dw)):
        assert bn % 8 == 0 and bv % 128 == 0
        assert plan.n_pad % bn == 0 and plan.v_pad % bv == 0
        for save_s in (True, False):
            assert xk._vmem_bytes(kernel, (bn, bv), d, item, item,
                                  save_s) <= xk._VMEM_BUDGET


@pytest.mark.parametrize("n,v,want", [
    (10, 100, ((16, 128), 16, 128)),     # rows to 8, vocabulary to 128
    (8, 16, ((8, 128), 8, 128)),
    (24, 300, ((24, 384), 24, 384)),
])
def test_plan_clamps_small_problems(n, v, want):
    from tpudml.ops.xent_kernel import _plan

    plan = _plan(n, 16, v, jnp.float32, jnp.float32)
    assert (plan.tile, plan.n_pad, plan.v_pad) == want
    assert plan.dw == plan.tile


@pytest.mark.parametrize("bn,bv", [(8, 128), (256, 2048), (512, 1024),
                                   (16, 384)])
def test_plan_honours_explicit_blocks(bn, bv):
    from tpudml.ops.xent_kernel import _padded_dims, _plan

    n, d, v = 8192, 1024, 50257
    plan = _plan(n, d, v, jnp.bfloat16, jnp.bfloat16, bn, bv)
    assert plan.tile == (bn, bv)
    assert (plan.n_pad, plan.v_pad) == _padded_dims(n, v, bn, bv)[2:]
    assert plan.dw[0] == bn and plan.v_pad % plan.dw[1] == 0
    # one of the two given: the other is the plan's
    rows = _plan(n, d, v, jnp.bfloat16, jnp.bfloat16, bn, None)
    cols = _plan(n, d, v, jnp.bfloat16, jnp.bfloat16, None, bv)
    open_ = _plan(n, d, v, jnp.bfloat16, jnp.bfloat16)
    assert rows.tile == (bn, open_.tile[1]) and cols.tile[1] == bv


@pytest.mark.parametrize("d,bn,bv,v,want", [
    (8192, 128, 384, 1536, 128),     # a wide model: 384 over the budget,
                                     # 256 does not divide 1536
    (1024, 256, 2048, 50257, 2048),  # the given tile fits: kept
    (16, 8, 64, 64, 64),             # a tile under 128 lanes: kept whole
])
def test_plan_dw_tile_is_a_fitting_divisor(d, bn, bv, v, want):
    """dW's vocabulary tile: the widest 128-multiple DIVISOR of v_pad that
    fits VMEM at the row block (halving could strand a 384 above its cap),
    and the given tile where nothing narrower exists."""
    from tpudml.ops.xent_kernel import _plan

    plan = _plan(8192, d, v, jnp.float32, jnp.float32, bn, bv)
    assert plan.dw == (bn, want) and plan.v_pad % want == 0


@pytest.mark.parametrize("n,d,v,blocks", [
    (8192, 1024, 50257, (None, None)),
    (2050, 16, 4500, (None, None)),
    (100, 16, 1000, (16, 256)),
])
def test_plan_dims_agree_forward_backward_and_auto_save_s(
        n, d, v, blocks, monkeypatch):
    """The residual the forward writes, the one the backward expects and
    the one ``save_s=None`` weighs are the same [n_pad, v_pad]."""
    from tpudml.ops import xent_kernel as xk

    f32 = jnp.float32
    plan = xk._plan(n, d, v, f32, f32, *blocks)
    x, w = jax.ShapeDtypeStruct((n, d), f32), jax.ShapeDtypeStruct((d, v), f32)
    b, y = jax.ShapeDtypeStruct((v,), f32), jax.ShapeDtypeStruct((n,), jnp.int32)
    lse, picked, s = jax.eval_shape(
        lambda *a: xk._fused_forward(*a, *blocks, True, save_s=True),
        x, w, b, y)
    assert s.shape == (plan.n_pad, plan.v_pad)
    # the backward asserts the residual's shape against ITS plan
    jax.eval_shape(
        lambda *a: xk._fused_backward_saved(*a, *blocks, True),
        x, w, b, y, lse, s, jax.ShapeDtypeStruct((), f32))
    residual = plan.n_pad * plan.v_pad * 4
    monkeypatch.setattr(xk, "SAVE_S_AUTO_MAX_BYTES", residual)
    assert xk._auto_save_s(n, d, v, f32, f32, *blocks) is True
    monkeypatch.setattr(xk, "SAVE_S_AUTO_MAX_BYTES", residual - 1)
    assert xk._auto_save_s(n, d, v, f32, f32, *blocks) is False


@pytest.mark.parametrize("n,d,v", [(2050, 16, 4500), (1100, 8, 2300)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_plans_own_tiles_many_blocks_match_reference(n, d, v, dtype):
    """The blocks left open, at a problem of more than one row block AND
    more than one vocabulary tile (ragged in both): loss and all three
    gradients against ``_reference_xent``; lean == save-s bit for bit."""
    from tpudml.ops import xent_kernel as xk

    plan = xk._plan(n, d, v, dtype, dtype)
    for bn, bv in (plan.tile, plan.dw):
        assert plan.n_pad // bn > 1 and plan.v_pad // bv > 1
    kx, kw, kb, ky = jax.random.split(jax.random.PRNGKey(7), 4)
    x = jax.random.normal(kx, (n, d), jnp.float32).astype(dtype)
    w = (jax.random.normal(kw, (d, v), jnp.float32) * 0.2).astype(dtype)
    b = (jax.random.normal(kb, (v,), jnp.float32) * 0.2).astype(dtype)
    y = jax.random.randint(ky, (n,), 0, v)

    def run(save_s):
        return jax.value_and_grad(
            lambda x, w, b: xk.linear_cross_entropy(
                x, w, y, b, interpret=True, save_s=save_s),
            argnums=(0, 1, 2))(x, w, b)

    loss_s, grads_s = run(True)
    loss_l, grads_l = run(False)
    assert float(loss_s) == float(loss_l)
    for gs, gl in zip(grads_s, grads_l):
        np.testing.assert_array_equal(np.asarray(gs), np.asarray(gl))
    want, want_grads = jax.value_and_grad(
        lambda x, w, b: xk._reference_xent(x, w, b, y), argnums=(0, 1, 2)
    )(x.astype(jnp.float32), w.astype(jnp.float32), b.astype(jnp.float32))
    tol = dict(rtol=1e-5, atol=1e-6) if dtype == jnp.float32 else dict(
        rtol=5e-2, atol=2e-4)
    np.testing.assert_allclose(float(loss_s), float(want),
                               rtol=tol["rtol"], atol=1e-5 if dtype == jnp.float32 else 2e-2)
    for g, r in zip(grads_s, want_grads):
        np.testing.assert_allclose(np.asarray(g, np.float32), np.asarray(r),
                                   **tol)
