"""Parity tests for the grouped-dW Pallas kernel and the ragged_ffn
custom_vjp (tpudml/ops/moe_kernel.py).

The oracle for ``grouped_dw`` is the stock masked transpose — exactly
what ``lax.ragged_dot``'s VJP computes: per expert, mask rows outside
the group's slab and contract ``x^T @ g``. The kernel must reproduce it
through the Pallas interpreter (uneven groups, empty experts, rows that
straddle tile boundaries, bf16 inputs with f32 accumulation), and the
``ragged_ffn`` backward must be grad-identical to differentiating the
plain ragged composition.

Cheapest variants run tier-1; the multi-tiling interpreter sweep is
slow-marked (the interpreter re-traces per tiling).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from tpudml.core.prng import seed_key
from tpudml.ops.moe_kernel import grouped_dw, ragged_ffn

E = 8


def _stock_dw(x, g, group_sizes):
    """The masked-transpose oracle (what ragged_dot's VJP computes)."""
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    rows = jnp.arange(x.shape[0])[:, None]
    out = []
    for i in range(group_sizes.shape[0]):
        m = ((rows >= starts[i]) & (rows < ends[i])).astype(x.dtype)
        out.append((x * m).T @ (g * m))
    return jnp.stack(out)


def _operands(key, m, k, n):
    k1, k2 = jax.random.split(key)
    return (
        jax.random.normal(k1, (m, k), jnp.float32),
        jax.random.normal(k2, (m, n), jnp.float32),
    )


# Uneven groups including empty experts and tile-straddling boundaries.
GROUPS = {
    "uneven": jnp.array([3, 11, 2, 17, 9, 5, 12, 5], jnp.int32),
    "empty": jnp.array([20, 0, 10, 0, 14, 0, 20, 0], jnp.int32),
    "collapsed": jnp.array([64, 0, 0, 0, 0, 0, 0, 0], jnp.int32),
}
# The collapsed slab accumulates one expert across many sequential tile
# partials, so its sum association differs from the oracle's single
# masked dot by an extra f32 ulp or two — everything else holds 1e-6.
ATOL = {"uneven": 1e-6, "empty": 1e-6, "collapsed": 5e-6}


@pytest.mark.parametrize("groups", sorted(GROUPS))
def test_grouped_dw_reference_matches_stock(groups):
    gs = GROUPS[groups]
    x, g = _operands(seed_key(0), int(jnp.sum(gs)), 16, 24)
    np.testing.assert_allclose(
        np.asarray(grouped_dw(x, g, gs)),  # reference path on CPU
        np.asarray(_stock_dw(x, g, gs)),
        rtol=1e-5,
        atol=1e-6,
    )


@pytest.mark.parametrize("groups", sorted(GROUPS))
def test_grouped_dw_interpret_matches_stock(groups):
    gs = GROUPS[groups]
    x, g = _operands(seed_key(1), int(jnp.sum(gs)), 16, 24)
    got = grouped_dw(x, g, gs, tiling=(16, 128, 128), interpret=True)
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(_stock_dw(x, g, gs)),
        rtol=1e-5,
        atol=ATOL[groups],
    )


@pytest.mark.slow
@pytest.mark.parametrize("tm", [8, 16, 32])
@pytest.mark.parametrize("groups", sorted(GROUPS))
def test_grouped_dw_interpret_tiling_sweep(groups, tm):
    """Boundary visits must stay correct for every row-tile size: groups
    smaller than a tile, straddling tiles, and owning many tiles."""
    gs = GROUPS[groups]
    x, g = _operands(seed_key(2), int(jnp.sum(gs)), 16, 24)
    got = grouped_dw(x, g, gs, tiling=(tm, 128, 128), interpret=True)
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(_stock_dw(x, g, gs)),
        rtol=2e-5,
        atol=ATOL[groups],
    )


def test_grouped_dw_ignores_tail_rows():
    """Rows beyond sum(group_sizes) are unowned padding and must not
    leak into any expert's tile."""
    gs = jnp.array([5, 0, 9, 2, 0, 3, 1, 4], jnp.int32)  # sums to 24
    x, g = _operands(seed_key(3), 40, 16, 24)  # 16 junk tail rows
    want = _stock_dw(x, g, gs)
    for kwargs in ({}, {"tiling": (8, 128, 128), "interpret": True}):
        np.testing.assert_allclose(
            np.asarray(grouped_dw(x, g, gs, **kwargs)),
            np.asarray(want),
            rtol=1e-5,
            atol=1e-6,
        )


def test_grouped_dw_bf16_in_f32_accum():
    gs = GROUPS["empty"]
    x, g = _operands(seed_key(4), int(jnp.sum(gs)), 16, 24)
    xb, gb = x.astype(jnp.bfloat16), g.astype(jnp.bfloat16)
    want = _stock_dw(xb.astype(jnp.float32), gb.astype(jnp.float32), gs)
    got = grouped_dw(xb, gb, gs, tiling=(8, 128, 128), interpret=True)
    assert got.dtype == jnp.float32  # accumulator dtype survives to the output
    # One f32 ulp of reassociation on this XLA: 2 of 3072 elements differ
    # by 1.19e-7 absolute (2.8e-6 relative) from the stock contraction.
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=5e-6)


def test_grouped_dw_validates_operands():
    x = jnp.zeros((8, 4))
    with pytest.raises(ValueError, match="row-aligned"):
        grouped_dw(x, jnp.zeros((9, 4)), jnp.array([8], jnp.int32))
    with pytest.raises(ValueError, match="integer"):
        grouped_dw(x, jnp.zeros((8, 4)), jnp.array([8.0]))


def _ffn_inputs(key, m, d, h, gs):
    ks = jax.random.split(key, 6)
    e = gs.shape[0]
    eids = jnp.repeat(jnp.arange(e), gs, total_repeat_length=m)
    return dict(
        x=jax.random.normal(ks[0], (m, d)),
        w1=jax.random.normal(ks[1], (e, d, h)) * 0.2,
        b1=jax.random.normal(ks[2], (e, h)) * 0.2,
        w2=jax.random.normal(ks[3], (e, h, d)) * 0.2,
        b2=jax.random.normal(ks[4], (e, d)) * 0.2,
        onehot=jax.nn.one_hot(eids, e, dtype=jnp.float32),
        dout=jax.random.normal(ks[5], (m, d)),
    )


def _stock_ffn(x, w1, b1, w2, b2, onehot, gs):
    h = jax.nn.relu(lax.ragged_dot(x, w1, gs) + onehot @ b1)
    return lax.ragged_dot(h, w2, gs) + onehot @ b2


@pytest.mark.parametrize("groups", ["uneven", "empty"])
def test_ragged_ffn_grads_match_stock(groups):
    """The hand-written VJP (grouped dW, ragged_dot dx/dh, one-hot db)
    must be grad-identical to differentiating the plain composition."""
    gs = GROUPS[groups]
    v = _ffn_inputs(seed_key(5), int(jnp.sum(gs)), 16, 32, gs)
    args = (v["x"], v["w1"], v["b1"], v["w2"], v["b2"], v["onehot"])

    np.testing.assert_allclose(
        np.asarray(ragged_ffn(*args, gs)),
        np.asarray(_stock_ffn(*args, gs)),
        rtol=1e-5,
        atol=1e-6,
    )
    g_new = jax.grad(
        lambda *a: jnp.vdot(ragged_ffn(*a, gs), v["dout"]), argnums=range(6)
    )(*args)
    g_old = jax.grad(
        lambda *a: jnp.vdot(_stock_ffn(*a, gs), v["dout"]), argnums=range(6)
    )(*args)
    for name, a, b in zip(["dx", "dw1", "db1", "dw2", "db2"], g_new, g_old):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6, err_msg=name
        )
    # onehot is integer-derived: our VJP returns zeros for it by contract.
    assert not np.any(np.asarray(g_new[5]))


@pytest.mark.slow
def test_ragged_ffn_interpret_grads_match_stock():
    """Same parity with the Pallas interpreter doing both dW kernels,
    under jit (the vjp must trace cleanly inside a jitted step)."""
    gs = GROUPS["empty"]
    v = _ffn_inputs(seed_key(6), int(jnp.sum(gs)), 16, 32, gs)
    args = (v["x"], v["w1"], v["b1"], v["w2"], v["b2"], v["onehot"])

    g_new = jax.jit(
        jax.grad(
            lambda *a: jnp.vdot(
                ragged_ffn(*a, gs, (8, 128, 128), True), v["dout"]
            ),
            argnums=(1, 3),
        )
    )(*args)
    g_old = jax.grad(
        lambda *a: jnp.vdot(_stock_ffn(*a, gs), v["dout"]), argnums=(1, 3)
    )(*args)
    for name, a, b in zip(["dw1", "dw2"], g_new, g_old):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6, err_msg=name
        )
