"""Pallas fused-attention tests (interpret mode on the CPU harness).

Load-bearing property: the kernels are the same function as the reference
``dot_product_attention`` — forward (all block sizes, causal on/off,
bfloat16) in both its forms, a head resident in VMEM and K/V tiles streamed
(the rule between them is ``_forward_plan``), the resident kernels in both
operand forms — the model's own [B, T, H·D] rows, a lane block of whole heads
a program, and each head folded first (``_heads_per_block``) — and gradients
via every backward path: the one-pass kernel (a head resident in VMEM), the dQ and
dK/dV kernels that stream tiles (``_backward_plan``), and the custom_vjp
reference-recompute fallback.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudml.models import TransformerLM
from tpudml.nn.attention import dot_product_attention
from tpudml.ops import (
    attention_kernel,
    flash_attention,
    flash_block_grads,
    flash_forward_lse,
)

B, T, H, D = 2, 32, 4, 8


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(11)
    return tuple(
        jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
        for _ in range(3)
    )


def _kernel_names(fn) -> set:
    return set(re.findall(r"name=(flash_\w+)", str(jax.make_jaxpr(fn)())))


def _takes_resident(t, d, dtype, block_q, block_k, k_shift=0) -> bool:
    forward = attention_kernel._forward_plan(t, d, dtype, block_q, block_k, k_shift)[0]
    assert forward in (attention_kernel._forward_resident,
                       attention_kernel._flash_forward)
    return forward is attention_kernel._forward_resident


FORWARD_FORMS = pytest.mark.parametrize(
    "streamed", [False, True], ids=["by_rule", "streaming"])


def _hold_forward(monkeypatch, streamed):
    """``streamed``: hold the forward to the kernel that streams K/V tiles,
    which long heads keep, at a shape whose rule is the resident one."""
    if streamed:
        monkeypatch.setattr(attention_kernel, "_resident_fits", lambda *a: False)


@FORWARD_FORMS
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block_q,block_k", [(8, 8), (16, 8), (32, 16), (8, 32)])
def test_kernel_matches_reference(qkv, monkeypatch, causal, block_q, block_k,
                                  streamed):
    _hold_forward(monkeypatch, streamed)
    assert _takes_resident(T, D, jnp.float32, block_q, block_k) != streamed
    q, k, v = qkv
    got = flash_attention(q, k, v, causal=causal, block_q=block_q, block_k=block_k, interpret=True)
    want = dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)


def test_kernel_bfloat16(qkv):
    q, k, v = (a.astype(jnp.bfloat16) for a in qkv)
    got = flash_attention(q, k, v, causal=True, block_q=16, interpret=True)
    assert got.dtype == jnp.bfloat16
    want = dot_product_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal=True,
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), rtol=0.05, atol=0.02
    )


def test_gradients_match_reference(qkv):
    """The recompute FALLBACK path (blocked_backward=False) — the blocked
    kernels have their own parametrized test below."""
    q, k, v = qkv
    w = jnp.asarray(np.random.default_rng(3).normal(size=(B, T, H, D)).astype(np.float32))
    got = jax.grad(
        lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, causal=True, block_q=16, interpret=True,
                            blocked_backward=False) * w
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    want = jax.grad(
        lambda q, k, v: jnp.sum(dot_product_attention(q, k, v, causal=True) * w),
        argnums=(0, 1, 2),
    )(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-4, atol=1e-6)


@FORWARD_FORMS
@pytest.mark.parametrize(
    "t,block_q,block_k,causal",
    [
        (30, 16, 512, False),
        (30, 16, 512, True),
        # 35 padded Q rows: the last Q tile's diagonal lies past the K tile
        (32, 5, 512, True),
        # Multiple K tiles WITH K padding: the padded-tail mask must apply
        # at global k positions across tiles (kj > 0).
        (30, 16, 8, False),
        (30, 16, 8, True),
        (27, 8, 4, True),  # 4 x 7 tiles: more pairs than the resident form unrolls
    ],
)
def test_odd_lengths_pad_and_mask(qkv, monkeypatch, t, block_q, block_k, causal,
                                  streamed):
    """Any T works via pad-and-mask (never by shrinking the MXU block):
    padded keys get no attention mass, padded queries are sliced off."""
    _hold_forward(monkeypatch, streamed)
    assert _takes_resident(t, D, jnp.float32, block_q, block_k) == (
        not streamed and (t, block_q, block_k) != (27, 8, 4))
    q, k, v = (a[:, :t] for a in qkv)
    got = flash_attention(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k, interpret=True
    )
    want = dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)


def _flash_and_reference_grads(q, k, v, causal, **blocks):
    w = jnp.asarray(
        np.random.default_rng(7).normal(size=q.shape).astype(np.float32)
    )
    got = jax.grad(
        lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, causal=causal, interpret=True, **blocks)
            .astype(jnp.float32) * w
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    want = jax.grad(
        lambda q, k, v: jnp.sum(dot_product_attention(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), causal=causal) * w),
        argnums=(0, 1, 2),
    )(q, k, v)
    return got, want


def _takes_one_pass(t, d, dtype, block_q, block_k) -> bool:
    calls = attention_kernel._backward_plan(t, d, dtype, block_q, block_k)[0]
    assert calls in (attention_kernel._backward_one_pass,
                     attention_kernel._backward_calls)
    return calls is attention_kernel._backward_one_pass


# (t, block_q, block_k, causal, one_pass): multi-tile grids, odd lengths (K
# padding masked at global positions), tiles off the square, causal
# skipping; 4 x 7 tiles are more pairs than the one-pass kernel unrolls.
BACKWARD_TILINGS = [
    (32, 8, 8, False, True), (32, 8, 8, True, True), (30, 16, 8, True, True),
    (27, 8, 4, False, False), (32, 16, 8, True, True), (32, 8, 16, True, True),
    (30, 8, 16, False, True), (27, 16, 16, True, True),
]


@pytest.mark.parametrize("held", [None, "_one_pass_fits", "_resident_fits"],
                         ids=["by_rule", "two_kernels", "streaming_forward"])
@pytest.mark.parametrize("t,block_q,block_k,causal,one_pass", BACKWARD_TILINGS)
def test_blocked_backward_matches_reference(qkv, monkeypatch, t, block_q, block_k,
                                            causal, one_pass, held):
    """The flash backward must reproduce reference gradients across
    multi-tile loops, odd lengths, and causal skipping: in the forms the
    rules give the shape (the resident forward and the one-pass backward for
    all but one), with the backward held to the dQ and dK/dV kernels, which
    long sequences keep, and with the forward held to its streaming kernel:
    either forward's lse feeds either backward."""
    if held:
        monkeypatch.setattr(attention_kernel, held, lambda *a: False)
    q, k, v = (a[:, :t] for a in qkv)
    assert _takes_one_pass(t, D, q.dtype, block_q, block_k) == (
        one_pass and held != "_one_pass_fits")
    assert _takes_resident(t, D, q.dtype, block_q, block_k) == (
        one_pass and held != "_resident_fits")
    got, want = _flash_and_reference_grads(
        q, k, v, causal, block_q=block_q, block_k=block_k)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=5e-4, atol=1e-5)


def test_one_pass_backward_takes_lse_at_its_own_padding(qkv):
    """A (forward, backward) pair of Q tiles that pad T differently: the
    forward's lse has 24 rows, the backward's resident block 32."""
    q, k, v = (a[:, :24] for a in qkv)
    assert _takes_one_pass(24, D, q.dtype, 16, 8)
    got, want = _flash_and_reference_grads(q, k, v, True, block_q=(8, 16), block_k=8)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=5e-4, atol=1e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 5e-4), (jnp.bfloat16, 0.03)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("t,causal", [(256, True), (200, True), (200, False)])
def test_one_pass_backward_at_head_widths(t, causal, d, dtype, tol):
    """Head 64 and 128 at the default tiles, T a multiple of the tile and
    not (``t_valid`` masking), bf16 and float32: relative error of each
    gradient against the float32 reference."""
    rng = np.random.default_rng(d + t)
    q, k, v = (jnp.asarray(rng.normal(size=(1, t, 2, d)), dtype) for _ in range(3))
    assert _takes_one_pass(t, d, dtype, None, None)
    got, want = _flash_and_reference_grads(q, k, v, causal)
    for g, r in zip(got, want):
        assert g.dtype == dtype
        err = np.linalg.norm(np.asarray(g, np.float32) - np.asarray(r))
        assert err <= tol * np.linalg.norm(np.asarray(r))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 0.01)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("t,causal", [(1024, True), (600, True), (600, False)])
def test_resident_forward_at_head_widths(t, causal, d, dtype, tol):
    """Head 64 and 128 at the default tile (2 x 2 of 512 rows), T a multiple
    of it and not (padded keys masked, padded rows cut), bf16 and float32:
    the output against the float32 reference, and output and lse against the
    streaming kernel's at the same tiles (the same operations on the same
    tiles in the same order: a rounding of the CPU's matmul apart here)."""
    rng = np.random.default_rng(d + t)
    q, k, v = (jnp.asarray(rng.normal(size=(1, t, 1, d)), dtype) for _ in range(3))
    forward, block_q, block_k = attention_kernel._forward_plan(t, d, dtype, None, None)
    assert forward is attention_kernel._forward_resident
    assert block_q == block_k == attention_kernel._RESIDENT_TILE
    out, lse = forward(q, k, v, causal, block_q, block_k, True)
    want = dot_product_attention(*(a.astype(jnp.float32) for a in (q, k, v)),
                                 causal=causal)
    assert out.dtype == dtype and lse.dtype == jnp.float32
    assert lse.shape == (1, 1, 1, 1024)  # rows: lane-dense, not a [T, 1] column
    err = np.linalg.norm(np.asarray(out, np.float32) - np.asarray(want))
    assert err <= tol * np.linalg.norm(np.asarray(want))
    s_out, s_lse = attention_kernel._flash_forward(
        q, k, v, causal, block_q, block_k, True)
    gap = np.linalg.norm(np.asarray(out, np.float32) - np.asarray(s_out, np.float32))
    assert gap <= tol * np.linalg.norm(np.asarray(want))
    np.testing.assert_allclose(np.asarray(lse[0, 0, :, :t]), np.asarray(s_lse[:, :t, 0]),
                               rtol=2e-5, atol=2e-6)


def _rel_err(got, want) -> float:
    want = np.asarray(want, np.float32)
    return float(np.linalg.norm(np.asarray(got, np.float32) - want)
                 / np.linalg.norm(want))


# (H, D, heads a lane block): which operand form the resident kernels take
# follows (H, D) alone — the model's own [B, T, H·D] rows where they split into
# lane blocks of whole heads, else each head folded to rows of its own.
ROW_FORMS = [(4, 64, 2), (3, 64, 0), (2, 128, 1), (8, 32, 4), (6, 32, 0),
             (1, 256, 1), (2, 96, 0)]


@pytest.mark.parametrize("h,d,heads", ROW_FORMS)
def test_operand_form_follows_heads_and_width(h, d, heads):
    assert attention_kernel._heads_per_block(h, d) == heads


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("tiles,dtype,tol", [
    (None, jnp.float32, 5e-4), (None, jnp.bfloat16, 0.03), (16, jnp.float32, 5e-4),
], ids=["one_tile-f32", "one_tile-bf16", "3x3_tiles-f32"])
@pytest.mark.parametrize("h,d,heads", ROW_FORMS[:5],
                         ids=["d64_pair", "d64_odd_h_fold", "d128", "d32_four",
                              "d32_h6_fold"])
def test_resident_forms_match_reference(h, d, heads, tiles, causal, dtype, tol):
    """Both operand forms of the resident kernels, forward and backward,
    against ``dot_product_attention``: heads that share a lane block (a pair
    at D 64, four at D 32), a head a block (D 128) and the folded heads of a
    shape whose rows do not split; T 37 padded to one tile of 40 rows and to
    3 x 3 tiles of 16 (padded keys masked, padded rows cut; the CPU's
    interpreter refuses bf16 there, in a causal backward of one head a
    block, at the parent too); the output, and dQ, dK, dV, which hang on the
    forward's lse rows and the Δ taken inside."""
    t = 37
    rng = np.random.default_rng(h * d)
    q, k, v = (jnp.asarray(rng.normal(size=(2, t, h, d)), dtype) for _ in range(3))
    assert _takes_resident(t, d, dtype, tiles, tiles)
    assert attention_kernel._backward_plan(t, d, dtype, tiles, tiles, heads)[0] is (
        attention_kernel._backward_one_pass)
    blocks = dict(block_q=tiles, block_k=tiles)
    suffix = "_rows" if heads else ""
    assert _kernel_names(lambda: jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, causal=causal, interpret=True, **blocks).astype(jnp.float32)))(q)
    ) == {"flash_fwd_resident" + suffix, "flash_bwd" + suffix}
    out = flash_attention(q, k, v, causal=causal, interpret=True, **blocks)
    want = dot_product_attention(*(a.astype(jnp.float32) for a in (q, k, v)),
                                 causal=causal)
    assert out.dtype == dtype and out.shape == q.shape
    assert _rel_err(out, want) <= tol / 3
    got, ref = _flash_and_reference_grads(q, k, v, causal, **blocks)
    for g, r in zip(got, ref):
        assert g.dtype == dtype and g.shape == q.shape
        assert _rel_err(g, r) <= tol


@pytest.mark.parametrize("h,d", [(4, 64), (2, 128), (8, 32)],
                         ids=["d64_pair", "d128", "d32_four"])
def test_rows_and_folded_forms_agree(monkeypatch, h, d):
    """The same shape through both operand forms (the folded one held by
    the rule's own function): the output and lse to the last bit — a head's
    scores over its block's lanes add the other heads' zeros, exactly
    nothing, and P·V's kept lanes are the head's own dot products — and the
    gradients to float32 rounding (Δ sums a head's lanes among the block's,
    in another order than alone)."""
    rng = np.random.default_rng(d)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 48, h, d)), jnp.float32)
               for _ in range(3))

    def run():
        out, lse = attention_kernel._forward_resident(q, k, v, True, 16, 16, True)
        grads, _ = _flash_and_reference_grads(q, k, v, True, block_q=16, block_k=16)
        return out, lse.reshape(2, h, 48), grads

    rows = run()
    monkeypatch.setattr(attention_kernel, "_heads_per_block", lambda h, d: 0)
    jax.clear_caches()  # the resident calls are jitted: the rule is read at the trace
    folded = run()
    monkeypatch.undo()
    jax.clear_caches()  # and no later test meets the folded trace of this shape
    np.testing.assert_array_equal(np.asarray(rows[0]), np.asarray(folded[0]))
    np.testing.assert_array_equal(np.asarray(rows[1]), np.asarray(folded[1]))
    for a, b in zip(rows[2], folded[2]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("held,forward,backward", [
    ("_one_pass_fits", "flash_fwd_resident_rows", {"flash_bwd_dq", "flash_bwd_dkv"}),
    ("_resident_fits", "flash_fwd", {"flash_bwd_rows"}),
], ids=["rows_forward_two_kernels", "streaming_forward_rows_backward"])
def test_lse_passes_between_the_forms(monkeypatch, held, forward, backward):
    """The resident forward's lse is rows, the streaming forward's a column
    a head; either backward takes either (a head too long for one side of
    the rule and not the other: float32 at T 2048 x D 128)."""
    monkeypatch.setattr(attention_kernel, held, lambda *a: False)
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 37, 2, 64)), jnp.float32)
               for _ in range(3))
    assert _kernel_names(lambda: jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, causal=True, interpret=True, block_q=16, block_k=16)))(q)
    ) == {forward} | backward
    got, want = _flash_and_reference_grads(q, k, v, True, block_q=16, block_k=16)
    for g, r in zip(got, want):
        assert _rel_err(g, r) <= 5e-4


def test_rows_form_per_shard_with_heads_sharded():
    """Under a GSPMD engine's layout the kernels run per shard, and the rule
    sees the shard's heads: 4 heads of 64 over 2 devices are a pair a shard
    (the rows form), 2 heads over 2 devices one head a shard (folded)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpudml.parallel.sharding import kernel_layout

    mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
    rng = np.random.default_rng(9)
    for h, names in ((4, {"flash_fwd_resident_rows", "flash_bwd_rows"}),
                     (2, {"flash_fwd_resident", "flash_bwd"})):
        q, k, v = (jax.device_put(
            jnp.asarray(rng.normal(size=(2, 40, h, 64)), jnp.float32),
            NamedSharding(mesh, P(None, None, "model", None))) for _ in range(3))

        def grads(q, k, v):
            with kernel_layout(mesh, head="model"):
                return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
                    q, k, v, causal=True, interpret=True) ** 2), (0, 1, 2))(q, k, v)

        assert _kernel_names(lambda: grads(q, k, v)) == names
        want = jax.grad(lambda q, k, v: jnp.sum(dot_product_attention(
            q, k, v, causal=True) ** 2), (0, 1, 2))(q, k, v)
        for g, r in zip(jax.jit(grads)(q, k, v), want):
            assert _rel_err(g, r) <= 5e-4


@pytest.mark.parametrize(
    "t,d,dtype,block_q,block_k,k_shift,resident",
    [
        (1024, 64, jnp.bfloat16, None, None, 0, True),    # gpt2-medium.pretrain-1k
        (512, 64, jnp.bfloat16, None, None, 0, True),
        (2048, 64, jnp.bfloat16, None, None, 0, True),    # 16 pairs
        (2048, 128, jnp.bfloat16, None, None, 0, True),   # the chip smoke's row
        (2048, 128, jnp.float32, None, None, 0, True),    # 13.6 MB of 16.8
        (2560, 64, jnp.bfloat16, None, None, 0, False),   # 25 pairs to unroll
        (4096, 128, jnp.bfloat16, None, None, 0, False),
        (8192, 128, jnp.bfloat16, None, None, 0, False),  # starcoderbase-1b
        (1024, 64, jnp.bfloat16, 128, 128, 0, False),     # 64 pairs to unroll
        (2048, 64, jnp.bfloat16, 1024, 1024, 0, True),
        (4096, 64, jnp.bfloat16, 1024, 1024, 0, False),   # 16 pairs; head + score tile 23 MB
        (8192, 128, jnp.bfloat16, 2048, 2048, 0, False),  # 16 pairs; the head alone 25 MB
        (1024, 64, jnp.bfloat16, None, None, 1, False),   # a ring block's shifted diagonal
        (32, 8, jnp.float32, 8, 8, 0, True),
    ],
)
def test_forward_form_follows_the_shape(t, d, dtype, block_q, block_k, k_shift,
                                        resident):
    """The rule itself: which form a (T, head dim, dtype, tiles, shift)
    takes, and that a tile left open takes that form's default."""
    _, bq, bk = attention_kernel._forward_plan(t, d, dtype, block_q, block_k, k_shift)
    assert _takes_resident(t, d, dtype, block_q, block_k, k_shift) == resident
    if block_q is None:
        (stream_bq, _), stream_bk = attention_kernel._default_blocks(d)
        tile = attention_kernel._RESIDENT_TILE
        assert (bq, bk) == ((tile, tile) if resident else (stream_bq, stream_bk))
    else:
        assert (bq, bk) == (block_q, block_k)


def test_forward_lse_keeps_the_streaming_kernel(qkv):
    """Ring attention's and the serving prefill's per-block entry point
    stays on the streaming kernel at a shape whose whole-sequence forward is
    resident (the serving programs must not change), and both name their
    kernel."""
    q, k, v = qkv
    assert _takes_resident(T, D, q.dtype, None, None)

    assert _kernel_names(lambda: flash_forward_lse(
        q, k, v, causal=True, interpret=True)) == {"flash_fwd"}
    assert _kernel_names(lambda: flash_attention(
        q, k, v, causal=True, interpret=True)) == {"flash_fwd_resident"}


@pytest.mark.parametrize(
    "t,d,dtype,block_q,block_k,one_pass",
    [
        (1024, 64, jnp.bfloat16, None, None, True),    # gpt2-medium.pretrain-1k
        (512, 64, jnp.bfloat16, None, None, True),
        (2048, 64, jnp.bfloat16, None, None, True),
        (2048, 128, jnp.bfloat16, None, None, True),   # the chip smoke's row
        (1024, 128, jnp.float32, None, None, True),
        (2048, 128, jnp.float32, None, None, False),   # float32 doubles the head
        (4096, 128, jnp.bfloat16, None, None, False),
        (8192, 128, jnp.bfloat16, None, None, False),  # starcoderbase-1b
        (1024, 64, jnp.bfloat16, 128, 128, False),     # 64 tile pairs to unroll
        (32, 8, jnp.float32, 8, 8, True),
    ],
)
def test_backward_form_follows_the_shape(t, d, dtype, block_q, block_k, one_pass):
    """The rule itself: which form a (T, head dim, dtype, tiles) takes, and
    that a tile left open takes that form's default."""
    _, bq, bk, t_pad_q, t_pad_k = attention_kernel._backward_plan(
        t, d, dtype, block_q, block_k)
    assert _takes_one_pass(t, d, dtype, block_q, block_k) == one_pass
    assert t_pad_q % bq == 0 and t_pad_k % bk == 0 and t_pad_q >= t <= t_pad_k
    if block_q is None:
        two_kernel_bq = attention_kernel._default_blocks(d)[0][1]
        assert bq == (attention_kernel._ONE_PASS_TILE if one_pass else two_kernel_bq)
    else:
        assert (bq, bk) == (block_q, block_k)


def test_block_grads_keep_the_two_kernels(qkv):
    """Ring context parallelism's per-block entry point (external lse and Δ,
    ``k_shift``) stays on the dQ and dK/dV kernels at a shape whose full
    backward is one pass, and the blocks still sum to the full gradient."""
    q, k, v = qkv
    w = jnp.asarray(np.random.default_rng(7).normal(size=q.shape).astype(np.float32))
    o = dot_product_attention(q, k, v, causal=False)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    lse = jax.nn.logsumexp(s, axis=-1)
    delta = jnp.sum(w * o, axis=-1).transpose(0, 2, 1)
    half = T // 2
    halves = (slice(0, half), slice(half, T))

    def block(i, j):
        """Q block i against K/V block j, as the ring runs them."""
        return flash_block_grads(
            q[:, halves[i]], k[:, halves[j]], v[:, halves[j]], w[:, halves[i]],
            lse[:, :, halves[i]], delta[:, :, halves[i]],
            block_q=8, block_k=8, interpret=True)

    assert _takes_one_pass(half, D, q.dtype, 8, 8)
    assert _kernel_names(lambda: block(0, 1)) == {"flash_bwd_dq", "flash_bwd_dkv"}
    g = [[block(i, j) for j in (0, 1)] for i in (0, 1)]
    got = (
        jnp.concatenate([g[i][0][0] + g[i][1][0] for i in (0, 1)], 1),
        jnp.concatenate([g[0][j][1] + g[1][j][1] for j in (0, 1)], 1),
        jnp.concatenate([g[0][j][2] + g[1][j][2] for j in (0, 1)], 1),
    )
    want = jax.grad(
        lambda q, k, v: jnp.sum(dot_product_attention(q, k, v, causal=False) * w),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=5e-4, atol=1e-5)


@pytest.mark.skipif(jax.default_backend() != "cpu", reason="CPU dispatch path")
def test_cpu_dispatch_falls_back_to_reference(qkv):
    """interpret=None off-TPU must use the reference math (not the slow
    interpreter): identical values by construction."""
    q, k, v = qkv
    got = flash_attention(q, k, v, causal=True)
    want = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_transformer_flash_impl_matches_full():
    tokens = jnp.asarray(
        np.random.default_rng(5).integers(0, 50, size=(B, T)).astype(np.int32)
    )
    base = dict(vocab_size=50, embed_dim=32, num_heads=4, num_layers=2, max_len=T)
    full = TransformerLM(**base)
    flash = TransformerLM(**base, impl="flash")
    params, _ = full.init(jax.random.key(0))
    np.testing.assert_allclose(
        np.asarray(jax.jit(lambda p, t: flash(p, t))(params, tokens)),
        np.asarray(jax.jit(lambda p, t: full(p, t))(params, tokens)),
        rtol=2e-4,
        atol=1e-5,
    )
