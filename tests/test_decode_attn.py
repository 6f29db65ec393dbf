"""The decode-attention kernel (``ops/decode_attn.py``) in interpret mode
against the einsums it replaces, and the predicate that chooses it.

What Mosaic makes of it — the packed K/V heads read with no relayout, the
tiles, VMEM — is ``tests/test_tpu_compile.py``'s; what it costs is a chip
run's (PERF.md §6, PR 31).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudml.nn.attention import decode_attention, decode_attention_grouped
from tpudml.ops import decode_attn as da
from tpudml.serve import cache

B, L, BLOCK, D = 4, 64, 16, 128
# Per slot: the first row, a block's last row, mid-block, the last row.
POS = jnp.array([0, BLOCK - 1, 2 * BLOCK + 5, L - 1])
HEADS = [(16, 1), (32, 2), (8, 4)]
TOL = {jnp.float32: 2e-6, jnp.bfloat16: 2e-2}


def _operands(h, hkv, dtype, seed=0):
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(kq, (B, 1, h, D), dtype)
    k = jax.random.normal(kk, (B, L, hkv, D), dtype)
    v = jax.random.normal(kv, (B, L, hkv, D), dtype)
    return q, k, v


def _close(got, want, tol):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("h,hkv", HEADS, ids=["16over1", "32over2", "8over4"])
def test_kernel_matches_both_einsums(h, hkv, dtype):
    """Several row blocks a slot, each slot at its own depth: the kernel,
    the grouped einsum and the einsum over K/V repeated to every query
    head agree (float32 to rounding; bf16 to a bf16 output's step)."""
    q, k, v = _operands(h, hkv, dtype)
    got = da.decode_attn(q, k, v, POS, block=BLOCK, interpret=True)
    assert got.shape == q.shape and got.dtype == q.dtype
    _close(got, decode_attention_grouped(q, k, v, POS), TOL[dtype])
    rep = [jnp.repeat(a, h // hkv, axis=2) for a in (k, v)]
    _close(got, decode_attention(q, *rep, POS), TOL[dtype])


@pytest.mark.parametrize("h,hkv", HEADS, ids=["16over1", "32over2", "8over4"])
def test_stale_rows_carry_no_weight(h, hkv):
    """Rows past ``pos`` hold whatever an evicted request left: large and
    finite here. The answer is that of the written prefix alone."""
    q, k, v = _operands(h, hkv, jnp.float32, seed=1)
    written = (jnp.arange(L)[None, :] <= POS[:, None])[:, :, None, None]
    want = da.decode_attn(q, jnp.where(written, k, 0), jnp.where(written, v, 0),
                          POS, block=BLOCK, interpret=True)
    got = da.decode_attn(q, jnp.where(written, k, 3e4),
                         jnp.where(written, v, -3e4), POS, block=BLOCK,
                         interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.isfinite(np.asarray(got)).all()


@pytest.mark.parametrize("q_dtype,kv_dtype", [
    (jnp.float32, jnp.bfloat16), (jnp.bfloat16, jnp.float32)],
    ids=["f32_over_bf16_cache", "bf16_over_f32_cache"])
def test_operands_take_the_wider_type(q_dtype, kv_dtype):
    """A float32 model over a bf16 cache (and the reverse) computes in
    float32, as ``read_all``'s cast to the compute type does at least."""
    q, k, v = _operands(32, 2, jnp.float32, seed=2)
    q, k, v = q.astype(q_dtype), k.astype(kv_dtype), v.astype(kv_dtype)
    got = da.decode_attn(q, k, v, POS, block=BLOCK, interpret=True)
    want = decode_attention_grouped(*(a.astype(jnp.float32) for a in (q, k, v)),
                                    POS)
    assert got.dtype == q_dtype
    _close(got, want, TOL[q_dtype])


def test_rows_must_fill_whole_blocks():
    q, k, v = _operands(16, 1, jnp.float32)
    with pytest.raises(ValueError, match="blocks of 24"):
        da.decode_attn(q, k, v, POS, block=24, interpret=True)


# kind, max_len, kv_heads, num_heads, head_dim -> the kernel or the einsum
PREDICATE = {
    "mqa_16x128": (("bf16", 8192, 1, 16, 128), True),
    "gqa_32x128_over_2": (("bf16", 4096, 2, 32, 128), True),
    "f32_cache": (("f32", 4096, 4, 8, 128), True),
    "short_cache_one_block": (("bf16", 48, 1, 2, 128), True),
    "head_256": (("bf16", 2048, 1, 8, 256), True),
    "head_64": (("bf16", 1024, 1, 16, 64), False),
    "head_96": (("bf16", 1024, 2, 16, 96), False),
    "mha": (("bf16", 8192, 16, 16, 128), False),
    "int8": (("int8", 8192, 1, 16, 128), False),
    "int8_sim": (("int8_sim", 8192, 1, 16, 128), False),
    "bf16_sim": (("bf16_sim", 8192, 1, 16, 128), False),
    "ragged_rows": (("bf16", 3000, 1, 16, 128), False),
    "three_kv_heads_ragged_block": (("bf16", 4096, 3, 6, 128), False),
    "short_ragged_rows": (("bf16", 40, 1, 16, 128), False),
}


@pytest.mark.parametrize("case", PREDICATE)
def test_predicate_chooses_from_what_it_can_see(case, monkeypatch):
    args, kernel = PREDICATE[case]
    assert not cache.decode_kernel(*args), "no TPU here: every cache einsums"
    monkeypatch.setattr(da, "kernel_interpret", lambda: True)
    assert cache.decode_kernel(*args) is kernel
    if kernel:  # what the predicate lets through, the kernel takes
        max_len, kv_heads = args[1:3]
        assert max_len % da.block_rows(max_len, kv_heads) == 0


@pytest.mark.parametrize("kind,hkv,kernel", [
    ("f32", 1, True), ("bf16", 2, True), ("int8", 1, False), ("f32", 4, False)],
    ids=["f32_mqa", "bf16_gqa", "int8_mqa", "f32_mha"])
def test_apply_decode_takes_the_path_the_predicate_names(kind, hkv, kernel,
                                                         monkeypatch):
    """One decode step of the module over a cache with stale rows: with the
    kernel switched on (interpret) the layer's output is the einsum path's,
    and the kernel runs exactly where the predicate says."""
    from tpudml.nn.attention import MultiHeadAttention
    from tpudml.serve.cache import KVCache, _encode

    attn = MultiHeadAttention(512, 4, num_kv_heads=hkv, rope=True)
    params, _ = attn.init(jax.random.key(3))
    kx, kk, kv = jax.random.split(jax.random.key(4), 3)
    x = jax.random.normal(kx, (B, 1, 512))
    rows, scales = zip(*(_encode(jax.random.normal(key, (B, L, hkv, D)), kind)
                         for key in (kk, kv)))
    none = jnp.zeros((0,), jnp.float32)
    old = KVCache(k=rows[0], v=rows[1], kind=kind,
                  k_scale=none if scales[0] is None else scales[0],
                  v_scale=none if scales[1] is None else scales[1])
    want, want_cache = attn.apply_decode(params, old, x, POS)

    calls = []
    real = da.decode_attn
    monkeypatch.setattr(da, "kernel_interpret", lambda: True)
    monkeypatch.setattr(da, "decode_attn",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    got, got_cache = attn.apply_decode(params, old, x, POS)
    assert len(calls) == int(kernel)
    _close(got, want, 2e-2 if kind == "bf16" else 1e-5)
    np.testing.assert_array_equal(np.asarray(got_cache.k, np.float32),
                                  np.asarray(want_cache.k, np.float32))
