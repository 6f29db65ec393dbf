"""Serving parity: the incremental prefill/decode path is the SAME
function as the full forward.

Load-bearing properties:

- greedy decode through the KV cache (chunked prefill + per-token
  ``apply_decode``) reproduces the full-forward logits at every emitted
  position to 1e-5/1e-6 — dense, GQA, learned-position-table, and
  TP-sharded configs;
- the quantized cache kinds match their ``_sim`` oracles EXACTLY (the
  decode-side dequant is bitwise the write-side roundtrip) and track the
  full-precision logits loosely;
- the cache primitives (per-slot token writes, chunk writes, prefix
  reads) are position-exact and donation-safe.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudml.core.config import MeshConfig
from tpudml.core.dist import make_mesh
from tpudml.models import TransformerLM
from tpudml.serve import ServeConfig, ServingEngine, cache_bytes, init_cache
from tpudml.serve.cache import read_all, read_slot_prefix, write_chunk, write_token
from tpudml.serve.load import Request

V, D, HEADS, LAYERS, MAX_LEN = 48, 32, 4, 2, 32
RTOL, ATOL = 1e-5, 1e-6

CONFIGS = {
    "rope_dense": dict(rope=True),
    "rope_gqa": dict(rope=True, num_kv_heads=2),
    "pos_table": dict(rope=False),
    # head_dim 128: the widths at which the decode step writes its K/V
    # rows by one scatter a tensor (serve/cache.py:row_scatter)
    "mqa_dh128": dict(rope=False, embed_dim=256, num_heads=2, num_kv_heads=1),
    "rope_dh128": dict(rope=True, embed_dim=256, num_heads=2),
}


def _model(**kw):
    base = dict(vocab_size=V, embed_dim=D, num_heads=HEADS,
                num_layers=LAYERS, max_len=MAX_LEN)
    base.update(kw)
    return TransformerLM(**base)


def _prompt(n=11, seed=3):
    return np.random.default_rng(seed).integers(0, V, n).astype(np.int32)


def incremental_logits(model, params, prompt, steps, *, kind="f32", chunk=4,
                       slots=1):
    """Greedy-decode ``steps`` tokens through the cache path (chunked
    prefill of prompt[:-1], then token-by-token apply_decode in slot 0);
    returns (logits list, emitted tokens)."""
    caches = model.init_decode_cache(slots, MAX_LEN, kind)
    p = len(prompt) - 1
    for s0 in range(0, p, chunk):
        buf = np.zeros((1, chunk), np.int32)
        n = min(chunk, p - s0)
        buf[0, :n] = prompt[s0:s0 + n]
        caches = model.apply_prefill(
            params, caches, jnp.asarray(buf), jnp.asarray(0, jnp.int32), s0)
    pos = np.full(slots, p, np.int32)
    last = np.full(slots, prompt[-1], np.int32)
    logits_seq, toks = [], []
    for _ in range(steps):
        logits, caches = model.apply_decode(
            params, caches, jnp.asarray(last), jnp.asarray(pos))
        logits_seq.append(np.asarray(logits[0]))
        t = int(jnp.argmax(logits[0]))
        toks.append(t)
        last = np.full(slots, t, np.int32)
        pos = pos + 1
    return logits_seq, toks


def full_forward_logits(model, params, prompt, steps):
    """Greedy reference: re-run the FULL forward per emitted token."""
    toks = list(prompt)
    logits_seq, out = [], []
    for _ in range(steps):
        logits, _ = model.apply(params, {}, jnp.asarray([toks], jnp.int32))
        row = np.asarray(logits[0, -1])
        logits_seq.append(row)
        t = int(np.argmax(row))
        toks.append(t)
        out.append(t)
    return logits_seq, out


# ------------------------------------------------- greedy logit parity


@pytest.mark.parametrize("cfg", list(CONFIGS), ids=list(CONFIGS))
def test_greedy_decode_logits_match_full_forward(cfg):
    model = _model(**CONFIGS[cfg])
    params, _ = model.init(jax.random.key(0))
    prompt = _prompt()
    inc, toks_inc = incremental_logits(model, params, prompt, steps=9)
    ref, toks_ref = full_forward_logits(model, params, prompt, steps=9)
    assert toks_inc == toks_ref
    for a, b in zip(inc, ref):
        np.testing.assert_allclose(a, b, rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("chunk", [1, 2, 4, 8])
def test_prefill_chunk_size_invariance(chunk):
    """Any chunking of the same prompt (including chunk=1 and a padded
    uneven tail) lands the same cache → identical decode logits."""
    model = _model(rope=True, num_kv_heads=2)
    params, _ = model.init(jax.random.key(1))
    prompt = _prompt(n=11, seed=5)  # 10 prefilled tokens: uneven vs 4/8
    ref, _ = full_forward_logits(model, params, prompt, steps=5)
    inc, _ = incremental_logits(model, params, prompt, steps=5, chunk=chunk)
    for a, b in zip(inc, ref):
        np.testing.assert_allclose(a, b, rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("kind,sim", [("bf16", "bf16_sim"),
                                      ("int8", "int8_sim")])
def test_quantized_cache_matches_sim_oracle(kind, sim):
    """The real quantized cache must equal its roundtrip-in-f32 twin
    BITWISE (dequant is deterministic), and track the full-precision
    logits loosely — the lossy-storage contract."""
    model = _model(rope=True, num_kv_heads=2)
    params, _ = model.init(jax.random.key(2))
    prompt = _prompt(seed=7)
    real, toks_real = incremental_logits(model, params, prompt, 7, kind=kind)
    oracle, toks_sim = incremental_logits(model, params, prompt, 7, kind=sim)
    assert toks_real == toks_sim
    for a, b in zip(real, oracle):
        np.testing.assert_allclose(a, b, rtol=RTOL, atol=ATOL)
    ref, _ = full_forward_logits(model, params, prompt, 7)
    for a, b in zip(real, ref):
        np.testing.assert_allclose(a, b, rtol=0, atol=0.25)


# ----------------------------------------------------------- TP parity


@pytest.mark.parametrize("cfg", ["rope_dense", "rope_gqa", "rope_dh128"])
def test_tp_decode_logits_match_full_forward(cfg):
    """The shard_map TP decode step (params via tensor_parallel_rules,
    cache sharded over kv_heads) is logit-exact against the unsharded
    full forward."""
    mesh = make_mesh(MeshConfig({"model": 2}), jax.devices()[:2])
    model = _model(**CONFIGS[cfg])
    params, _ = model.init(jax.random.key(3))
    prompt = _prompt(seed=9)
    scfg = ServeConfig(slots=2, max_len=MAX_LEN, prefill_chunk=4)
    eng = ServingEngine(model, params, scfg, mesh=mesh, axis_name="model")
    pos0, last0 = eng._admit(0, Request(rid=0, prompt=prompt,
                                        max_new_tokens=6))
    pos = np.array([pos0, 0], np.int32)
    last = np.array([last0, 0], np.int32)
    ref, toks_ref = full_forward_logits(model, params, prompt, steps=6)
    for i in range(6):
        next_t, logits, eng.caches = eng._decode(
            eng.params, eng.caches, jnp.asarray(last), jnp.asarray(pos))
        np.testing.assert_allclose(np.asarray(logits[0]), ref[i],
                                   rtol=RTOL, atol=ATOL)
        assert int(next_t[0]) == toks_ref[i]
        last = np.array([int(next_t[0]), 0], np.int32)
        pos = pos + np.array([1, 0], np.int32)


def test_tp_rejects_non_dividing_heads():
    mesh = make_mesh(MeshConfig({"model": 2}), jax.devices()[:2])
    model = _model(rope=True, num_heads=3, embed_dim=36, num_kv_heads=3)
    params, _ = model.init(jax.random.key(0))
    with pytest.raises(ValueError, match="divisible"):
        ServingEngine(model, params,
                      ServeConfig(slots=1, max_len=MAX_LEN, prefill_chunk=4),
                      mesh=mesh, axis_name="model")


# ------------------------------------------------------ cache primitives


def test_write_token_per_slot_positions():
    cache = init_cache(3, 8, 2, 4, "f32")
    k = jnp.arange(3 * 2 * 4, dtype=jnp.float32).reshape(3, 1, 2, 4)
    pos = jnp.asarray([0, 3, 7], jnp.int32)
    cache = write_token(cache, k, -k, pos)
    kk, vv = read_all(cache, jnp.float32)
    for b, p in enumerate([0, 3, 7]):
        np.testing.assert_array_equal(np.asarray(kk[b, p]),
                                      np.asarray(k[b, 0]))
        np.testing.assert_array_equal(np.asarray(vv[b, p]),
                                      np.asarray(-k[b, 0]))
        # every other row untouched
        mask = np.ones(8, bool)
        mask[p] = False
        assert np.all(np.asarray(kk[b])[mask] == 0)


def _write_token_oracle(cache, k_new, v_new, pos):
    """``write_token`` as it was before the row scatter (PR 29): one
    ``dynamic_update_slice`` per slot, which clamps ``pos`` into
    [0, L - Q]."""
    from jax import lax

    from tpudml.serve.cache import KVCache, _encode

    def put(buf, rows):
        at = (0,) * (buf.ndim - 2)
        return jax.vmap(
            lambda c, r, p: lax.dynamic_update_slice(c, r, (p, *at))
        )(buf, rows, pos)

    ks, kscale = _encode(k_new, cache.kind)
    vs, vscale = _encode(v_new, cache.kind)
    k_sc, v_sc = cache.k_scale, cache.v_scale
    if cache.kind == "int8":
        k_sc, v_sc = put(k_sc, kscale), put(v_sc, vscale)
    return KVCache(k=put(cache.k, ks), v=put(cache.v, vs),
                   k_scale=k_sc, v_scale=v_sc, kind=cache.kind)


@pytest.mark.parametrize("q", [1, 4], ids=["q1", "q4"])
@pytest.mark.parametrize("hkv,dh", [(1, 128), (8, 128), (16, 64)],
                         ids=["1x128", "8x128", "16x64"])
@pytest.mark.parametrize("kind", ["f32", "bf16", "int8"])
def test_write_token_bit_identical_to_per_slot_update(kind, hkv, dh, q):
    """Every element of the cache after ``write_token`` — codes and, for
    int8, scales — equals the per-slot ``dynamic_update_slice`` form's,
    for rows at 0, odd, even, the last legal start ``L - Q``, and out of
    range on both sides (clamped, as ``dynamic_update_slice`` clamps)."""
    from tpudml.serve.cache import KVCache

    length = 24
    pos = jnp.asarray([0, 5, 8, length - q, length - q + 1, length + 7, -3,
                       13], jnp.int32)
    b = pos.shape[0]
    rng = np.random.default_rng(hkv * dh + q)
    blank = init_cache(b, length, hkv, dh, kind)

    def noise(x):  # a used cache: stale rows everywhere
        return jnp.asarray(rng.integers(-100, 100, x.shape), x.dtype)

    cache = KVCache(k=noise(blank.k), v=noise(blank.v),
                    k_scale=noise(blank.k_scale),
                    v_scale=noise(blank.v_scale), kind=kind)
    k_new = jnp.asarray(rng.standard_normal((b, q, hkv, dh)), jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((b, q, hkv, dh)), jnp.float32)
    got = jax.jit(write_token)(cache, k_new, v_new, pos)
    want = jax.jit(_write_token_oracle)(cache, k_new, v_new, pos)
    for name in ("k", "v", "k_scale", "v_scale"):
        g, w = np.asarray(getattr(got, name)), np.asarray(getattr(want, name))
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(
            g.view(np.uint8), w.view(np.uint8), err_msg=name)
    # the write landed: slot 1's rows 5 .. 5+q-1 changed, row 4 did not
    assert not np.array_equal(np.asarray(got.k[1, 5:5 + q]),
                              np.asarray(cache.k[1, 5:5 + q]))
    np.testing.assert_array_equal(np.asarray(got.k[1, 4]),
                                  np.asarray(cache.k[1, 4]))


def test_write_chunk_targets_one_slot():
    cache = init_cache(2, 8, 1, 2, "f32")
    k = jnp.ones((1, 4, 1, 2))
    cache = write_chunk(cache, k, 2 * k, jnp.asarray(1, jnp.int32), 4)
    kk, vv = read_all(cache, jnp.float32)
    assert np.all(np.asarray(kk[0]) == 0)  # slot 0 untouched
    assert np.all(np.asarray(kk[1, 4:8]) == 1)
    assert np.all(np.asarray(vv[1, 4:8]) == 2)
    assert np.all(np.asarray(kk[1, :4]) == 0)
    pk, _ = read_slot_prefix(cache, jnp.asarray(1, jnp.int32), 6, jnp.float32)
    assert pk.shape == (1, 6, 1, 2)
    assert np.all(np.asarray(pk[0, 4:6]) == 1)


def test_int8_cache_shrinks_storage():
    f32 = init_cache(2, 16, 2, 8, "f32")
    i8 = init_cache(2, 16, 2, 8, "int8")
    # 4 bytes -> 1 byte per element + f32 scales per (token, head)
    assert cache_bytes(i8) < cache_bytes(f32) / 2


def test_cache_buffers_are_donation_distinct():
    """k/v (and scales) must be separate buffers — the engine donates
    the cache pytree every step and XLA rejects double-donation."""
    cache = init_cache(1, 4, 1, 2, "int8")
    ptrs = {x.unsafe_buffer_pointer()
            for x in (cache.k, cache.v, cache.k_scale, cache.v_scale)}
    assert len(ptrs) == 4
