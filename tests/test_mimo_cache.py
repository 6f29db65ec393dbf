"""Second half of `tests/test_mimo.py` (one file is one worker's work under
`--dist loadfile`): the ring and the kernel, the experts' shares, the engine.

The pattern model's window / full attention mixture (`tpudml.models.HybridLM`
kinds `F`, `W`, `D` and gated `E`: two K/V head counts, a q/k head wider than
the v head, a rotary slice, a value scale, a sink, a ring cache beside the full
one, SwiGLU experts with a held share) against its plain reference
(`benchmarks/reference/mimo_v2.py`, the yardstick's: one text serves the tests
and `correct`), at a small size in float32.

Load-bearing properties:

- `apply` equals the reference's forward for each published layer kind and the
  seven-layer model;
- prefill in chunks LONGER than the window, prompts longer than two windows,
  a padded tail, then decode through the caches, gives the reference's logits at
  every position, also in a slot taken over from a finished request;
- every mechanism matters: with its fault planted (sink, window edge, rotary
  width, value scale, head widths, ring wrap-around) the same comparison fails;
- the ring cache equals a `max_len` cache under the window mask, row for row,
  and the kernel (interpreted) reads both widths, the sink and the ring;
- the four held shares' parts add up to the uncut expert layer, in the program
  and the reference alike;
- the engine's `serve/dispatch` span says which forms the step runs and counts
  the live rows of each cache kind.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.drivers import mimo_adapter
from benchmarks.reference import mimo_v2 as ref
from benchmarks.tests.toy_mimo import TOY_MIMO
from tpudml.capabilities import TABLE, CompositionError
from tpudml.nn import MultiHeadAttention, SigmoidMoE
from tpudml.obs.tracer import Tracer, use_tracer
from tpudml.serve import cache as kv
from tpudml.serve.engine import ServeConfig, ServingEngine
from tpudml.serve.load import Request

@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def setup(cfg=TOY_MIMO, seed=5, **options):
    w = ref.init_weights(cfg, ref.seed_key(seed))
    return w, mimo_adapter.build_model(cfg, options), mimo_adapter.to_program(w, cfg)


def one_layer(window: int, moe: int, **kw) -> dict:
    return {**TOY_MIMO, "num_hidden_layers": 1, "hybrid_layer_pattern": [window],
            "moe_layer_freq": [moe], **kw}


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, TOY_MIMO["vocab_size"], n).astype(np.int32)


# ------------------------------------------------------ the ring and the kernel


def _window_layer(kv_heads=2, head_dim=24, v_dim=16, window=8, rotary=8):
    m = MultiHeadAttention(32, 4, causal=True, num_kv_heads=kv_heads, head_dim=head_dim,
                           use_bias=False, rope=True, rope_base=1e4, v_head_dim=v_dim,
                           rotary_dim=rotary, value_scale=0.707, window=window, sink=True)
    p, _ = m.init(jax.random.key(1))
    p["sink"] = 1.0 + jax.random.normal(jax.random.key(2), (4,))
    return m, p


def test_the_ring_equals_a_max_len_cache_under_the_window_mask_row_for_row():
    """The same layer over a ring of its window and over a 48-row cache (where
    nothing wraps and the einsum masks by position): the same outputs in
    prefill and decode, and every ring row holds what the long cache holds at
    the position `ring_positions` names."""
    m, p = _window_layer()
    x = jax.random.normal(jax.random.key(3), (2, 40, 32))
    ring, long = kv.init_cache(2, 8, 2, 24, "f32", 16), kv.init_cache(2, 48, 2, 24, "f32", 16)
    want, _ = m.apply(p, {}, x)
    for b in range(2):
        for s0, n in ((0, 16), (16, 5)):  # 21 tokens: a whole chunk and a padded one
            chunk = jnp.zeros((1, 16, 32)).at[:, :n].set(x[b:b + 1, s0:s0 + n])
            a, ring = m.apply_prefill(p, ring, chunk, jnp.asarray(b), s0, jnp.asarray(n))
            c, long = m.apply_prefill(p, long, chunk, jnp.asarray(b), s0, jnp.asarray(n))
            np.testing.assert_allclose(a[0, :n], c[0, :n], atol=1e-6)
            np.testing.assert_allclose(a[0, :n], want[b, s0:s0 + n], atol=1e-5)
    for t in range(21, 40):
        pos = jnp.full((2,), t, jnp.int32)
        a, ring = m.apply_decode(p, ring, x[:, t:t + 1], pos)
        c, long = m.apply_decode(p, long, x[:, t:t + 1], pos)
        np.testing.assert_allclose(a, c, atol=1e-6)
        np.testing.assert_allclose(a[:, 0], want[:, t], atol=1e-5)
        at = np.asarray(kv.ring_positions(pos, 8))  # [2, 8]
        assert at.min() == t - 7 and at.max() == t  # no row outside [pos - 7, pos]
        for b in range(2):
            np.testing.assert_array_equal(np.asarray(ring.k[b]), np.asarray(long.k[b, at[b]]))
            np.testing.assert_array_equal(np.asarray(ring.v[b]), np.asarray(long.v[b, at[b]]))


@pytest.mark.parametrize("window,kv_heads", [(16, 2), (None, 2), (16, 1)],
                         ids=["ring", "full", "ring-one-kv-head"])
def test_the_kernel_reads_both_widths_the_sink_and_the_ring(window, kv_heads, monkeypatch):
    """The decode kernel (interpreted) on a 192-wide key stored in 256 lanes
    beside a 128-wide value, with and without sink and ring, against `apply`;
    the einsum read of the same caches agrees."""
    from tpudml.ops import decode_attn

    m = MultiHeadAttention(32, 4, causal=True, num_kv_heads=kv_heads, head_dim=192,
                           use_bias=False, rope=True, v_head_dim=128, rotary_dim=64,
                           value_scale=0.707, window=window, sink=window is not None)
    p, _ = m.init(jax.random.key(1))
    if window:
        p["sink"] = 2.0 + jax.random.normal(jax.random.key(2), (4,))
    x = jax.random.normal(jax.random.key(3), (2, 40, 32))
    want, _ = m.apply(p, {}, x)
    outs = {}
    for form, interpret in (("einsum", None), ("kernel", True)):
        monkeypatch.setattr(decode_attn, "kernel_interpret", lambda: interpret)
        rows = window or 48
        assert kv.decode_kernel("f32", rows, kv_heads, 4, 256, 128) == (form == "kernel")
        cache = kv.init_cache(2, rows, kv_heads, kv.stored_width(192), "f32", 128)
        assert cache.k.shape[-1] == 256 and cache.v.shape[-1] == 128
        for b in range(2):
            for s0, n in ((0, 16), (16, 7)):
                chunk = jnp.zeros((1, 16, 32)).at[:, :n].set(x[b:b + 1, s0:s0 + n])
                _, cache = m.apply_prefill(p, cache, chunk, jnp.asarray(b), s0, jnp.asarray(n))
        got = []
        for t in range(23, 40):
            o, cache = m.apply_decode(p, cache, x[:, t:t + 1], jnp.full((2,), t, jnp.int32))
            got.append(o[:, 0])
        outs[form] = jnp.stack(got, axis=1)
        np.testing.assert_allclose(outs[form], want[:, 23:], atol=2e-5)
        assert float(jnp.abs(cache.k[..., 192:]).max()) == 0.0  # the lanes past the head
    np.testing.assert_allclose(outs["kernel"], outs["einsum"], atol=2e-5)


def test_levers_the_new_kinds_reject():
    """int8 storage for a ring (models/hybrid.py), and at the layer: the paged
    pool, the speculative window, ring / Ulysses attention."""
    _, model, _ = setup()
    with pytest.raises(CompositionError) as exc:
        model.init_decode_cache(2, 32, "int8")
    assert str(exc.value) == TABLE["serve_pattern_ring_int8"].message
    candidate = {"serve_pattern_window": True, "serve_cache_kind": "int8"}
    assert TABLE["serve_pattern_ring_int8"].when(candidate)
    assert not TABLE["serve_pattern_ring_int8"].when({**candidate, "serve_cache_kind": "bf16"})
    m, p = _window_layer()
    x = jnp.zeros((1, 2, 32))
    for call in (lambda: m.apply_decode_window(p, None, x, jnp.zeros((1,), jnp.int32)),
                 lambda: m.apply_decode_paged(p, None, None, x, jnp.zeros((1,), jnp.int32)),
                 lambda: m.apply_prefill_paged(p, None, None, x, 0)):
        with pytest.raises(ValueError, match="dense cache only"):
            call()
    with pytest.raises(ValueError, match="window, sink"):
        MultiHeadAttention(32, 4, causal=True, impl="ring", window=8)
    with pytest.raises(ValueError, match="causal"):
        MultiHeadAttention(32, 4, window=8)


# ------------------------------------------------------------------- experts


def _moe(cfg, held=None):
    return SigmoidMoE(cfg["hidden_size"], ref.router_width(cfg), cfg["num_experts_per_tok"],
                      cfg["moe_intermediate_size"], 0, 1.0, cfg["norm_topk_prob"], held,
                      gated=True)


def _share(params, first, count):
    ex = params["experts"]
    return {**params, "experts": {k: ex[k][first:first + count] for k in ex}}


@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_four_shares_add_up_to_the_uncut_layer(side):
    """Experts 0-3, 4-7, 8-11 and 12-15 of sixteen, top-4, no shared expert:
    the parts the four chips of a layer compute, summed, are the layer."""
    cfg = one_layer(1, 1, n_routed_experts=16, deployment={})
    w, _, params = setup(cfg)
    u = jax.random.normal(jax.random.key(10), (19, cfg["hidden_size"]))
    p = params["layer1"]["mixer"]
    lw = {k: a.astype(jnp.float32) for k, a in ref.layer_leaves(w, 0).items()}
    if side == "program":
        whole = _moe(cfg).forward(p, u)[0]
        parts = [_moe(cfg, (f, 4)).forward(_share(p, f, 4), u)[0] for f in (0, 4, 8, 12)]
    else:
        whole = ref.moe_mixer(cfg, lw, u)
        parts = [ref.moe_mixer(cfg, lw, u, held=(f, 4)) for f in (0, 4, 8, 12)]
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole), rtol=1e-5, atol=1e-6)
    assert all(float(jnp.abs(part - whole).max()) > 1e-3 for part in parts)


def test_a_held_share_of_gated_experts_matches_the_reference_given_the_same_share():
    cfg = one_layer(1, 1, n_routed_experts=16, deployment={})
    w, _, params = setup(cfg)
    u = jax.random.normal(jax.random.key(11), (13, cfg["hidden_size"]))
    lw = {k: a.astype(jnp.float32) for k, a in ref.layer_leaves(w, 0).items()}
    got, counts = _moe(cfg, (5, 6)).forward(_share(params["layer1"]["mixer"], 5, 6), u)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.moe_mixer(cfg, lw, u, held=(5, 6))),
                               rtol=1e-5, atol=1e-6)
    assert int(counts["routed"]) == 13 * 4 and 0 < int(counts["held"]) < 13 * 4
    assert "shared" not in params["layer1"]["mixer"]  # there is no shared expert to count


# -------------------------------------------------------------------- engine


def _requests(sizes):
    return [Request(rid=i, prompt=_tokens(n, 100 + i), max_new_tokens=m, arrival_time=0.0)
            for i, (n, m) in enumerate(sizes)]


def test_engine_serves_the_reference_through_reused_slots_and_counts_its_caches():
    """Five requests through three slots (two are taken over), prompts up to
    six windows in chunks of two windows: every served token is the
    reference's greedy choice along the program's routes, which are the
    reference's own; `serve/dispatch` counts the live rows of each cache kind
    and the bytes allocated, and says which forms the step runs."""
    w, model, params = setup()
    engine = ServingEngine(model, params,
                           ServeConfig(slots=3, max_len=64, prefill_chunk=16, cache_kind="f32"))
    reqs = _requests([(37, 10), (1, 12), (20, 9), (48, 14), (17, 5)])
    tracer = Tracer()
    with use_tracer(tracer):
        report = engine.run(reqs)
    for r in reqs:
        st = report.requests[r.rid]
        seq = np.concatenate([r.prompt, np.asarray(st.tokens[:-1], np.int32)])
        routes = np.concatenate(st.routes)
        assert st.finished is not None and routes.shape == (len(seq), 6 * 4)
        first = len(r.prompt) - 1
        logits, regret = ref.served_rows_logits(TOY_MIMO, w, jnp.asarray(seq), first,
                                                len(st.tokens), jnp.asarray(routes))
        assert float(regret.max()) == 0.0
        assert np.asarray(jnp.argmax(logits, axis=-1)).tolist() == st.tokens
    steps = [e.args for e in tracer.events if e.cat == "serve" and e.name == "dispatch"]
    by_step = {s["step"]: s for s in steps}
    assert all(s["cache_bytes_full"] == 2 * 3 * 64 * 2 * (24 + 16) * 4
               and s["cache_bytes_window"] == 5 * 3 * 8 * 4 * (24 + 16) * 4 for s in steps)
    assert all(s["rows_full"] == 2 * (s["rows"] + s["active"]) for s in steps)
    assert all(s["rows_window"] <= 5 * 8 * s["active"] for s in steps)
    last = by_step[max(by_step)]
    assert last["rows_window"] == 5 * 8 * last["active"]  # every ring has wrapped by then
    assert {(s["row_scatter"], s["decode_kernel"]) for s in steps} == {(0, 0)}  # head 24: neither
    commit = next(e.args for e in tracer.events if e.cat == "serve" and e.name == "commit")
    assert commit["moe_routed"] > commit["moe_held"] > 0


def test_the_forms_follow_the_stored_widths(monkeypatch):
    """`cache_forms` answers for every attention layer: the toy's 24-wide head
    takes neither fast path; the published 192 / 128 heads, stored 256 / 128,
    take the scatter, and the kernel where there is one to run."""
    from tpudml.ops import decode_attn

    _, model, _ = setup()
    assert model.cache_forms(64, "f32") == (False, False)
    wide = mimo_adapter.build_model({**TOY_MIMO, "head_dim": 192, "v_head_dim": 128}, {})
    assert wide.cache_forms(64, "bf16") == (True, False)
    monkeypatch.setattr(decode_attn, "kernel_interpret", lambda: False)
    assert wide.cache_forms(64, "bf16") == (True, False)  # a ring of 8 rows is no whole block
    roomy = mimo_adapter.build_model({**TOY_MIMO, "head_dim": 192, "v_head_dim": 128,
                                      "sliding_window": 16}, {})
    assert roomy.cache_forms(64, "bf16") == (True, True)
    assert roomy.cache_forms(64, "int8_sim") == (True, False)
    assert roomy.live_rows(np.array([3, 40]), 64) == {
        "rows_full": 2 * (4 + 41), "rows_window": 5 * (4 + 16),
        "rows_read_full": 2 * (4 + 41), "state_bytes": 0}  # each cache has one reader, no state
