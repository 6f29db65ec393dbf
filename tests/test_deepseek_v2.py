"""The pattern model's latent attention (`tpudml.models.HybridLM` kind `L`:
`LatentAttention` over a `LatentCache`, YaRN RoPE, the absorbed decode and its
kernel) and softmax group-limited routing beside a shared expert, against their
plain reference (`benchmarks/reference/deepseek_v2.py`, the yardstick's: one text
serves the tests and `correct`), at a small size in float32.

Load-bearing properties:

- `apply` (the published non-absorbed form) equals the reference's forward for a
  dense layer, an expert layer and the three-layer model;
- prefill in chunks with a padded tail, then the ABSORBED decode through the
  latent cache, gives the reference's full-forward logits at every position, also
  in a slot taken over from a longer request, and through `ServingEngine`;
- `decode_attn_latent` (interpreted) equals the absorbed einsum, and through the
  layer the non-absorbed form, reading ONE cache operand;
- YaRN's table, the attention factor and the softmax scale against hand-worked
  values; a factor of 1 is plain RoPE;
- the router against a loop-written group-limited selection, ties and an empty
  group included; the four held shares' routed parts plus the shared expert
  counted once are the uncut layer, in the program and the reference alike;
- the engine's spans carry the latent cache's counters and `moe_group_hit`, and a
  model without groups or latent layers keeps the counters it had.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.drivers import deepseek_v2_adapter as adapter
from benchmarks.reference import deepseek_v2 as ref
from benchmarks.tests.toy_deepseek_v2 import TOY
from tpudml.capabilities import TABLE
from tpudml.models import HybridLM
from tpudml.nn import attention as attn
from tpudml.nn.moe import SigmoidMoE
from tpudml.obs.tracer import Tracer, use_tracer
from tpudml.ops import decode_attn
from tpudml.serve import cache as kv
from tpudml.serve.engine import ServeConfig, ServingEngine
from tpudml.serve.load import Request

PUBLISHED_YARN = dict(dim=64, base=1e4, factor=40, original=4096, beta_fast=32, beta_slow=1)


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def setup(cfg=TOY, seed=5, **options):
    w = ref.init_weights(cfg, ref.seed_key(seed))
    return w, adapter.build_model(cfg, options), adapter.to_program(w, cfg)


def layers(n: int, dense: int, **kw) -> dict:
    return {**TOY, "num_hidden_layers": n, "first_k_dense_replace": dense, **kw}


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, TOY["vocab_size"], n).astype(np.int32)


# ------------------------------------------------------------- whole sequence


@pytest.mark.parametrize("cfg", [layers(1, 1), layers(1, 0), TOY],
                         ids=["latent+dense", "latent+experts", "three-layers"])
def test_apply_matches_the_reference(cfg):
    w, model, params = setup(cfg)
    tokens = _tokens(40)  # past the toy's original context of 16: every YaRN pair turns
    want = ref.forward(cfg, w, jnp.asarray(tokens))
    got, _ = model.apply(params, {}, jnp.asarray(tokens)[None])
    # float32 against float32 in another order of summation
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), rtol=1e-4, atol=2e-6)


def test_the_adapter_renames_every_leaf_and_builds_the_published_pattern():
    w, model, params = setup()
    assert model.pattern == "LDLELE" == adapter.pattern(TOY)
    init, _ = model.init(jax.random.key(0))
    assert jax.tree.structure(params) == jax.tree.structure(init)
    assert all(a.shape == b.shape and a.dtype == b.dtype
               for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(init)))
    assert params["layer3"]["mixer"]["experts"]["gate"] is w["layers.1.experts.gate"]
    assert "bias" not in params["layer3"]["mixer"]["router"]  # softmax scoring has none
    # W_UKV a head at a time: head h's [r, nope | v] block of the published matrix
    kv_up = params["layer0"]["mixer"]["kv_up"]["kernel"]
    assert kv_up.shape == (4, 32, 16 + 16)
    np.testing.assert_array_equal(np.asarray(kv_up[2]), np.asarray(w["layers.0.kv_b.w"][:, 64:96]))
    assert (model.held, model.num_experts, model.moe_groups, model.route_width) == (
        (4, 4), 16, (4, 2), 2 * 3)
    with pytest.raises(ValueError, match="pattern"):
        adapter.build_model({**TOY, "hybrid_override_pattern": "LD"}, {})


# -------------------------------------------------------------------- serving


def _serve(model, params, prompt, n_new, chunk=16, max_len=64, slot=1, slots=2, caches=None):
    """Prefill ``prompt`` (all but its last token) in chunks with a padded tail,
    then decode ``n_new`` tokens feeding the greedy choice back: (logits at every
    decode position [n_new, V], the sequence, the caches)."""
    caches = caches or model.init_decode_cache(slots, max_len, "f32")
    p = len(prompt) - 1
    for s0 in range(0, p, chunk):
        n = min(chunk, p - s0)
        padded = np.full((1, chunk), 7, np.int32)  # a tail that would show if it counted
        padded[0, :n] = prompt[s0:s0 + n]
        caches, _ = model.apply_prefill(params, caches, jnp.asarray(padded),
                                        jnp.asarray(slot, jnp.int32), s0, jnp.asarray(n))
    out, seq = [], list(prompt)
    for t in range(p, p + n_new):
        tokens = jnp.zeros((slots,), jnp.int32).at[slot].set(seq[t])
        pos = jnp.zeros((slots,), jnp.int32).at[slot].set(t)
        active = jnp.zeros((slots,), bool).at[slot].set(True)
        logits, caches, _, _ = model.apply_decode(params, caches, tokens, pos, active)
        out.append(logits[slot])
        seq.append(int(jnp.argmax(logits[slot])))
    return jnp.stack(out), np.asarray(seq, np.int32), caches


def _served_error(cfg, w, model, params, prompt, n_new=12, **kw):
    got, seq, caches = _serve(model, params, prompt, n_new, **kw)
    want = ref.forward(cfg, w, jnp.asarray(seq[:-1]))[len(prompt) - 1:]
    return float(jnp.abs(got - want).max()), caches


@pytest.mark.parametrize("n_prompt", [1, 9, 17, 33, 49])
def test_prefill_then_decode_gives_the_reference_logits_at_every_position(n_prompt):
    """Chunks of 16: no chunk, part of one, exactly one (17 = 16 + the token
    decode starts from), two and a chunk boundary, three. The decode step is the
    absorbed form over the cache; the reference the non-absorbed full forward."""
    w, model, params = setup()
    err, _ = _served_error(TOY, w, model, params, _tokens(n_prompt, n_prompt))
    assert err < 2e-5  # float32 both sides; the absorbed form sums in another order


def test_a_slot_taken_over_from_a_longer_request_serves_the_reference():
    """The latent cache is not zeroed when a slot changes hands: the rows a
    49-token request left behind lie past the next request's positions and the
    mask hides them, in prefill's padded tail and in decode."""
    w, model, params = setup()
    _, caches = _served_error(TOY, w, model, params, _tokens(49, 1))
    assert float(jnp.abs(caches[0].rows[1, 40:60]).max()) > 0  # stale rows are there
    for n in (5, 21):
        err, caches = _served_error(TOY, w, model, params, _tokens(n, n), caches=caches)
        assert err < 2e-5


def test_what_a_token_leaves_behind_is_the_normed_latent_beside_the_turned_key():
    """The cache row is `[RMSNorm(c_kv) | RoPE(k_r)]` at the token's position:
    32 + 8 values a layer, against 4 heads x (24 + 16) of keys and values."""
    w, model, params = setup(layers(1, 1))
    tokens = _tokens(12)
    _, _, caches = _serve(model, params, tokens, 1, chunk=4)
    lw = {k: a.astype(jnp.float32) for k, a in ref.layer_leaves(w, 0).items()}
    u = ref.rms_norm(w["embed"][tokens], lw["attn_norm.w"], TOY["rms_norm_eps"])
    down = u @ lw["kv_a.w"]
    inv, factor, _ = ref.rope_table(TOY)
    want = jnp.concatenate([ref.rms_norm(down[:, :32], lw["kv_a_norm.w"], TOY["rms_norm_eps"]),
                            ref.rope(down[:, 32:], inv, factor)], axis=-1)
    assert caches[0].rows.shape == (2, 64, 40) and caches[1] is None
    np.testing.assert_allclose(np.asarray(caches[0].rows[1, :12]), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    # the idle slot's decode write lands at its stale position 0, nowhere else
    assert float(jnp.abs(caches[0].rows[0, 1:]).max()) == 0.0


@pytest.mark.parametrize("plant", ["no_mscale", "plain_rope", "latent_before_norm",
                                   "key_before_rope"])
def test_a_planted_fault_in_one_mechanism_shows(plant):
    """The control tool's plants: each mechanism of the layer matters to the
    served logits, a hundred times the sound program's error and more."""
    from benchmarks.tools import control_deepseek_v2 as control

    w, model, params = setup()
    undo = control.plant(plant)
    try:
        assert _served_error(TOY, w, model, params, _tokens(38, 38))[0] > 1e-3
    finally:
        undo()
    assert _served_error(TOY, w, model, params, _tokens(38, 38))[0] < 2e-5


@pytest.mark.parametrize("option", [dict(moe_groups=None), dict(routed_scale=1.0),
                                    dict(norm_topk=True)], ids=lambda o: next(iter(o)))
def test_a_router_built_wrong_shows(option):
    w, model, params = setup(**option)
    got, _ = model.apply(params, {}, jnp.asarray(_tokens(40))[None])
    assert float(jnp.abs(got[0] - ref.forward(TOY, w, jnp.asarray(_tokens(40)))).max()) > 1e-3


# --------------------------------------------------------------------- kernel


def _latent_operands(b=3, length=64, heads=8, rank=128, rope=64, dtype=jnp.float32):
    width = kv.stored_width(rank + rope)
    rows = jax.random.normal(jax.random.key(0), (b, length, rank + rope))
    q = jax.random.normal(jax.random.key(1), (b, 1, heads, rank + rope))
    return (kv.fit_width(q, width).astype(dtype), kv.fit_width(rows, width).astype(dtype),
            jnp.asarray([0, 17, length - 1][:b]))


@pytest.mark.parametrize("block", [16, 32, 64])
def test_the_latent_kernel_is_the_absorbed_einsum(block):
    """One cache operand, read as key whole and as value in its first 128 lanes:
    row blocks of 16, 32 and the whole cache; positions at a block's first row,
    inside one and at the last."""
    q, rows, pos = _latent_operands()
    assert rows.shape[-1] == 256  # 192 values in whole tiles
    got = decode_attn.decode_attn_latent(q, rows, pos, v_dim=128, scale=0.11, block=block,
                                         interpret=True)
    keys = rows[:, :, None, :]
    want = attn.attention_by_position(q, keys, keys[..., :128], pos[:, None],
                                      jnp.arange(rows.shape[1]), scale=0.11)
    assert got.shape == (3, 1, 8, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)
    low = decode_attn.decode_attn_latent(q.astype(jnp.bfloat16), rows.astype(jnp.bfloat16), pos,
                                         v_dim=128, scale=0.11, block=block, interpret=True)
    assert low.dtype == jnp.bfloat16  # bfloat16 operands: a bfloat16 P, float32 statistics
    assert float(jnp.abs(low.astype(jnp.float32) - want).max()) < 0.03
    with pytest.raises(ValueError, match="decode_attn_latent"):
        decode_attn.decode_attn_latent(q, rows, pos, v_dim=128, scale=0.11, block=48)


def test_the_layer_reads_its_cache_with_the_kernel_and_equals_the_non_absorbed_form(monkeypatch):
    """A layer at widths the kernel takes (rank 128 + 64 rotary lanes, stored
    256): the decode step through the interpreted kernel, over rows that prefill
    wrote, against `apply` over the whole sequence, the published form."""
    layer = attn.LatentAttention(64, 4, 48, 128, 16, 64, 16, 100.0,
                                 (40, 16, 2, 0.25, 0.707, 0.707))
    params, _ = layer.init(jax.random.key(3))
    x = jax.random.normal(jax.random.key(4), (1, 33, 64))
    want, _ = layer.apply(params, {}, x)
    cache = kv.init_latent_cache(2, 64, layer.row_width, "f32")
    assert cache.rows.shape == (2, 64, 256)
    slot = jnp.asarray(1, jnp.int32)
    for s0 in (0, 16):
        out, cache = layer.apply_prefill(params, cache, x[:, s0:s0 + 16], slot, s0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want[:, s0:s0 + 16]),
                                   rtol=1e-4, atol=1e-5)
    calls = []
    real = decode_attn.decode_attn_latent
    monkeypatch.setattr(decode_attn, "decode_attn_latent",
                        lambda *a, **k: calls.append(k) or real(*a, **k))
    pos = jnp.asarray([0, 32])
    step = jnp.concatenate([x[:, :1], x[:, 32:33]])
    for interpret in (None, True):
        monkeypatch.setattr(decode_attn, "kernel_interpret", lambda: interpret)
        got, after = layer.apply_decode(params, cache, step, pos)
        np.testing.assert_allclose(np.asarray(got[1, 0]), np.asarray(want[0, 32]),
                                   rtol=1e-4, atol=1e-5)
    assert len(calls) == 1 and calls[0]["v_dim"] == 128
    assert calls[0]["scale"] == pytest.approx(80 ** -0.5 * attn.yarn_mscale(40, 0.707) ** 2)
    assert float(jnp.abs(after.rows[1, 32, :192]).max()) > 0 == float(
        jnp.abs(after.rows[..., 192:]).max())  # the stored row's last lanes stay zero


def test_the_forms_follow_the_stored_width(monkeypatch):
    """`cache_forms` answers for the latent cache as for one K/V head of the
    stored width: the toy's 40-wide row takes neither fast path; the published
    576, stored 640, takes the scatter, and the kernel where there is one."""
    _, model, _ = setup()
    assert model.cache_forms(64, "f32") == (False, False)
    wide = HybridLM(256, "LD", kv_rank=512, rope_dim=64, nope_dim=128, v_head_dim=128)
    caches = jax.eval_shape(lambda: wide.init_decode_cache(4, 4096, "bf16"))
    assert caches[0].rows.shape == (4, 4096, 640) and caches[0].rows.dtype == jnp.bfloat16
    assert wide.cache_bytes(caches) == {"cache_bytes_full": 0, "cache_bytes_window": 0,
                                        "cache_bytes_latent": 4 * 4096 * 640 * 2}
    assert wide.cache_forms(4096, "bf16") == (True, False)
    monkeypatch.setattr(decode_attn, "kernel_interpret", lambda: False)
    assert wide.cache_forms(4096, "bf16") == (True, True)
    assert wide.cache_forms(4096, "bf16_sim") == (True, False)
    assert wide.cache_forms(4000, "bf16") == (True, False)  # no whole row blocks
    assert wide.live_rows(np.array([9, 99]), 4096)["rows_latent"] == 110
    assert wide.prefill_entries == 1  # the dense layer behind the last cache writes nothing


def test_levers_the_latent_kind_rejects():
    _, model, params = setup()
    with pytest.raises(ValueError) as exc:
        model.init_decode_cache(2, 64, "int8")
    assert str(exc.value) == TABLE["serve_pattern_latent_int8"].message
    candidate = {"serve_pattern_latent": True, "serve_cache_kind": "int8"}
    assert TABLE["serve_pattern_latent_int8"].when(candidate)
    assert not TABLE["serve_pattern_latent_int8"].when({**candidate, "serve_cache_kind": "bf16"})
    with pytest.raises(ValueError, match="latent cache"):
        kv.init_latent_cache(2, 64, 40, "int8_sim")
    for key in ("serve_pattern_paged", "serve_pattern_spec", "serve_pattern_tp"):
        assert "latent" in TABLE[key].message
    from tpudml.serve.engine import ServeCompositionError

    with pytest.raises(ServeCompositionError) as exc:
        ServingEngine(model, params, ServeConfig(slots=2, max_len=64, cache_layout="paged",
                                                 page_size=8))
    assert str(exc.value) == TABLE["serve_pattern_paged"].message


# ----------------------------------------------------------------------- YaRN


def test_yarn_table_factor_and_scale_against_hand_worked_values():
    """The published record (theta 1e4, factor 40, 4096 positions, 32 and 1
    turns): pairs 0-10 keep their frequency, 23-31 are divided by 40, between
    them the ramp; m = 0.1 x 0.707 x ln 40 + 1."""
    table = attn.yarn_inv_freq(**PUBLISHED_YARN)
    plain = 1e4 ** (-2.0 * np.arange(32) / 64)
    # the pair that makes 32 turns over 4096 positions: 64 ln(4096 / 64 pi) / (2 ln 1e4)
    assert 64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(1e4)) == pytest.approx(
        10.47, abs=0.01)
    assert 64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(1e4)) == pytest.approx(22.51,
                                                                                       abs=0.01)
    np.testing.assert_allclose(table[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(table[23:], plain[23:] / 40, rtol=1e-6)
    keep = 1 - (16 - 10) / (23 - 10)  # pair 16, on the ramp
    assert table[16] == pytest.approx(plain[16] * (keep + (1 - keep) / 40), rel=1e-6)
    assert table.dtype == np.float32 and np.all(np.diff(table) < 0)
    np.testing.assert_allclose(table, np.asarray(ref.yarn_inv_freq(**PUBLISHED_YARN)), rtol=1e-6)
    assert attn.yarn_mscale(40, 0.707) == pytest.approx(1.2608, abs=5e-5)
    assert attn.yarn_mscale(1, 0.707) == 1.0 == attn.yarn_mscale(0.5, 1.0)
    layer = attn.LatentAttention(5120, 128, 1536, 512, 128, 64, 128, 1e4,
                                 (40, 4096, 32, 1, 0.707, 0.707))
    assert layer._scale == pytest.approx(192 ** -0.5 * 1.5896, rel=1e-4)
    assert layer.row_width == 576
    assert attn.LatentAttention(64, 4, 48, 32, 16, 8, 16)._scale == 24 ** -0.5  # no YaRN: m = 1


def test_a_yarn_factor_of_one_is_plain_rope_and_the_table_form_is_the_base_form():
    np.testing.assert_allclose(attn.yarn_inv_freq(**{**PUBLISHED_YARN, "factor": 1}),
                               1e4 ** (-2.0 * np.arange(32) / 64), rtol=1e-6)
    x = jax.random.normal(jax.random.key(2), (2, 5, 3, 8))
    at = jnp.asarray([[0, 1, 2, 3, 4], [7, 8, 9, 10, 11]])
    table = 100.0 ** (-jnp.arange(4, dtype=jnp.float32) / 4)
    np.testing.assert_array_equal(np.asarray(attn.rotary_embedding(x, at, 100.0)),
                                  np.asarray(attn.rotary_by_table(x, at, table)))
    np.testing.assert_allclose(np.asarray(attn.rotary_by_table(x, at[0], table, 1.5)),
                               1.5 * np.asarray(attn.rotary_embedding(x, at[0], 100.0)),
                               rtol=1e-5, atol=1e-6)  # 1.5 (a b) against (1.5 a) b
    # the reference turns the same lanes by the same angles
    inv, factor, m = ref.rope_table(TOY)
    layer = attn.LatentAttention(64, 4, 48, 32, 16, 8, 16, 100.0, adapter.yarn(TOY))
    np.testing.assert_allclose(np.asarray(layer._rope(x[:1], jnp.arange(5))[0]),
                               np.asarray(ref.rope(x[0], inv, factor)), rtol=1e-5, atol=1e-6)
    assert (factor, m) == (1.0, pytest.approx(1.2608, abs=5e-5))
    assert ref.softmax_scale(TOY) == pytest.approx(layer._scale)


# --------------------------------------------------------------------- router


def _loop_route(scores: np.ndarray, n_group: int, keep: int, k: int) -> list[list[int]]:
    """Group-limited greedy selection written as loops: a group's score is its
    largest, the best ``keep`` groups stay (the first of equals), the others are
    set to 0, the token's experts are the ``k`` largest of what is left (the
    first of equals)."""
    size = scores.shape[1] // n_group
    out = []
    for row in scores:
        best = [max(row[g * size:(g + 1) * size]) for g in range(n_group)]
        kept = sorted(range(n_group), key=lambda g: (-best[g], g))[:keep]
        left = [row[e] if e // size in kept else 0.0 for e in range(len(row))]
        out.append(sorted(range(len(row)), key=lambda e: (-left[e], e))[:k])
    return out


class _Scored(SigmoidMoE):
    """A layer whose scores are handed to it (frozen: set by the test)."""

    def scores(self, params, tokens):
        return self.given


def test_the_router_is_the_loop_written_group_limited_selection():
    moe = SigmoidMoE(64, 16, 3, 24, 48, 16.0, False, None, jnp.float32, True, "softmax", (4, 2))
    params, _ = moe.init(jax.random.key(0))
    u = jax.random.normal(jax.random.key(1), (200, 64))
    p = np.asarray(moe.scores(params, u))
    np.testing.assert_allclose(p.sum(axis=1), 1.0, rtol=1e-5)
    top, w = moe.route(params, u)
    assert np.asarray(top).tolist() == _loop_route(p, 4, 2, 3)
    np.testing.assert_allclose(np.asarray(w), 16.0 * np.take_along_axis(p, np.asarray(top), 1),
                               rtol=1e-6)  # the scores as they are, times the scale
    assert len({tuple(sorted({e // 4 for e in row})) for row in np.asarray(top).tolist()}) > 3
    free, _ = SigmoidMoE(64, 16, 3, 24, 48, 16.0, False, None, jnp.float32, True,
                         "softmax").route(params, u)
    assert np.asarray(free).tolist() != np.asarray(top).tolist()  # the limit binds
    lw = {"router.w": params["router"]["kernel"]}
    cfg = {**TOY, "deployment": {}, "n_routed_experts": 16}
    assert float(ref.route_regret(cfg, lw, u, top).max()) == 0.0
    assert float(ref.route_regret(cfg, lw, u, free).max()) > 0.01


def test_the_router_on_ties_and_an_empty_group():
    """Equal group maxima and equal experts go to the lower index; a group whose
    scores are all 0 can be kept (after the better ones) and its experts chosen
    only when nothing else is left."""
    given = np.zeros((4, 16), np.float32)
    given[0, [1, 5, 9, 13]] = 0.2          # four groups tie: groups 0 and 1 stay
    given[0, [0, 6]] = 0.1
    given[1, [12, 13, 14]] = [0.3, 0.3, 0.3]  # one group only; the second kept is empty
    given[2, 4] = 0.5                      # one expert in all: two of the three are zeros
    given[3, [2, 3, 8, 10, 15]] = [0.4, 0.4, 0.6, 0.1, 0.5]  # groups 2 and 3 beat group 0
    moe = _Scored(64, 16, 3, 24, 0, 1.0, False, None, jnp.float32, True, "softmax", (4, 2))
    object.__setattr__(moe, "given", jnp.asarray(given))
    params, _ = moe.init(jax.random.key(0))
    top, w = moe.route(params, jnp.zeros((4, 64)))
    assert np.asarray(top).tolist() == _loop_route(given, 4, 2, 3) == [
        [1, 5, 0], [12, 13, 14], [4, 0, 1], [8, 15, 10]]
    assert np.asarray(w)[2].tolist() == [0.5, 0.0, 0.0]
    kept = np.asarray(moe.kept_groups(jnp.asarray(given)))
    assert kept.tolist() == [[True, True, False, False], [True, False, False, True],
                             [True, True, False, False], [False, False, True, True]]
    # group_hit: the tokens that kept the held experts' group (group 3: experts 12-15)
    held = _Scored(64, 16, 3, 24, 0, 1.0, False, (12, 4), jnp.float32, True, "softmax", (4, 2))
    object.__setattr__(held, "given", jnp.asarray(given))
    params, _ = held.init(jax.random.key(0))
    _, counts = held.forward(params, jnp.zeros((4, 64)), jnp.asarray([True, True, True, False]))
    assert (int(counts["group_hit"]), int(counts["held"]), int(counts["routed"])) == (1, 3, 9)
    with pytest.raises(ValueError, match="groups"):
        SigmoidMoE(64, 16, 3, 24, 0, groups=(3, 2))
    with pytest.raises(ValueError, match="scoring"):
        SigmoidMoE(64, 16, 3, 24, 0, scoring="tanh")


def _share(params, first, count):
    ex = params["experts"]
    return {**params, "experts": {k: ex[k][first:first + count] for k in ex}}


@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_four_shares_add_up_to_the_uncut_layer(side):
    """Groups 0-3 of sixteen experts, a group a chip: the routed parts the four
    chips of a layer compute, summed, plus the shared expert (which every chip
    computes alike) counted once, are the uncut layer."""
    cfg = layers(1, 0, n_routed_experts=16, deployment={})
    w, model, params = setup(cfg)
    u = jax.random.normal(jax.random.key(10), (19, cfg["hidden_size"]))
    p = params["layer1"]["mixer"]
    lw = {k: a.astype(jnp.float32) for k, a in ref.layer_leaves(w, 0).items()}
    shared = ref.shared_expert(lw, u)
    if side == "program":
        whole = model._mixer("E").forward(p, u)[0]
        parts = [adapter.build_model({**cfg, "n_routed_experts": 4,
                                      "deployment": {"n_routed_experts": 16, "held_first": f}},
                                     {})._mixer("E").forward(_share(p, f, 4), u)[0] - shared
                 for f in (0, 4, 8, 12)]
    else:
        whole = ref.routed_experts(cfg, lw, u) + shared
        parts = [ref.routed_experts(cfg, lw, u, held=(f, 4)) for f in (0, 4, 8, 12)]
    np.testing.assert_allclose(np.asarray(sum(parts) + shared), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)
    assert all(float(jnp.abs(part + shared - whole).max()) > 1e-3 for part in parts)
    np.testing.assert_allclose(  # and the two sides agree on the whole
        np.asarray(whole), np.asarray(ref.routed_experts(cfg, lw, u) + shared), rtol=1e-5,
        atol=1e-6)


# -------------------------------------------------------------------- engine


def _requests(sizes):
    return [Request(rid=i, prompt=_tokens(n, 100 + i), max_new_tokens=m, arrival_time=0.0)
            for i, (n, m) in enumerate(sizes)]


def test_engine_serves_the_reference_through_reused_slots_and_counts_its_cache():
    """Five requests through three slots (two are taken over), prompts up to
    three chunks: every served token is the reference's greedy choice along the
    program's routes, which are the reference's own; `serve/dispatch` counts the
    latent caches' live rows and bytes, `serve/commit` the group hits."""
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
        assert st.finished is not None and routes.shape == (len(seq), 2 * 3)
        logits, regret = ref.served_rows_logits(TOY, w, jnp.asarray(seq), len(r.prompt) - 1,
                                                len(st.tokens), jnp.asarray(routes))
        assert float(regret.max()) == 0.0
        assert np.asarray(jnp.argmax(logits, axis=-1)).tolist() == st.tokens
    steps = [e.args for e in tracer.events if e.cat == "serve" and e.name == "dispatch"]
    assert all(s["cache_bytes_latent"] == 3 * 3 * 64 * 40 * 4 and s["cache_bytes_full"] == 0
               for s in steps)
    assert all(s["rows_latent"] == 3 * (s["rows"] + s["active"]) and s["rows_full"] == 0
               for s in steps)
    assert {(s["row_scatter"], s["decode_kernel"]) for s in steps} == {(0, 0)}  # 40 lanes
    commits = [e.args for e in tracer.events if e.cat == "serve" and e.name == "commit"]
    by_step = {s["step"]: s["active"] for s in steps}
    assert all(c["moe_routed"] == 2 * 3 * by_step[c["step"]] for c in commits)
    assert all(c["moe_held"] <= 3 * c["moe_group_hit"] <= 3 * c["moe_routed"] // 3
               for c in commits)
    hit = sum(c["moe_group_hit"] for c in commits) / (sum(c["moe_routed"] for c in commits) / 3)
    assert 0.3 < hit < 0.7  # two of four groups kept: a half in expectation


def test_a_model_without_groups_or_latent_layers_keeps_its_counters():
    plain = HybridLM(64, "ME*M")
    assert plain.counter_names == ("moe_routed", "moe_held", "experts_touched",
                                   "expert_load_max")
    assert set(plain.live_rows(np.array([3]), 16)) == {"rows_full", "rows_window",
                                                       "rows_read_full", "state_bytes"}
    caches = plain.init_decode_cache(2, 16)
    assert set(plain.cache_bytes(caches)) == {"cache_bytes_full", "cache_bytes_window"}
    assert kv.cache_bytes(caches[2]) == 2 * 2 * 16 * 2 * 16 * 4  # K and V, as before
    assert setup()[1].counter_names[-1] == "moe_group_hit"
    params, _ = plain.init(jax.random.key(0))
    assert "bias" in params["layer1"]["mixer"]["router"]  # sigmoid scoring keeps its bias
