"""The pattern model's Mamba-1 / differential-attention / shared-cache mixture
(`tpudml.models.HybridLM` kinds `S`, `G`, `X` and the differential `F` and `W`:
LayerNorm, a tied head, biases, a ring and ONE full cache that the cross layers
read, a prefill that stops behind it) against its plain reference
(`benchmarks/reference/phi4flash.py`, the yardstick's: one text serves the tests
and `correct`), at a small size in float32.

Load-bearing properties:

- `apply` equals the reference's forward at four, eight and twelve layers;
- prefill in chunks longer than the window with a padded tail, then decode
  through the caches, gives the reference's logits at every position, also in a
  slot taken over from another request (state reset, ring not read past its
  writer);
- a pair of K/V heads a cache row equals a head a row to float32 rounding, and the
  decode kernel (interpreted) reads pair-rows with `[q1 | 0]` and `[0 | q2]`;
- a prefill that stops behind the full layer's K and V leaves every cache and
  state as a prefill over the whole trunk does; a cross layer owns no cache and
  the shared one is written once a step;
- (in `tests/test_phi4flash_faults.py`: one file is one worker's work under
  `--dist loadfile`) every mechanism matters: with the cell's fault planted the
  comparison fails; the engine end to end, and its counters.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.drivers import phi4flash_adapter as adapter
from benchmarks.reference import phi4flash as ref
from benchmarks.tests.toy_phi4flash import TOY_PHI, served_error, serve, setup, tokens
from benchmarks.tools import control_phi4flash
from tpudml.models import HybridLM
from tpudml.ops import decode_attn

# Heads of 64, so that a pair is a 128-lane row and the kernel's path holds.
WIDE = {**TOY_PHI, "hidden_size": 512, "intermediate_size": 128, "sliding_window": 16,
        "assumed": {**TOY_PHI["assumed"], "mamba_expand": 1}}


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


# ------------------------------------------------------------- whole sequence


@pytest.mark.parametrize("layers", [4, 8, 12])
def test_apply_matches_the_reference(layers):
    cfg = {**TOY_PHI, "num_hidden_layers": layers}
    w, model, params = setup(cfg)
    sequence = tokens(40)  # five windows long
    want = ref.forward(cfg, w, jnp.asarray(sequence))
    got, _ = model.apply(params, {}, jnp.asarray(sequence)[None])
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), rtol=1e-4, atol=2e-4)


def test_the_adapter_renames_every_leaf_and_builds_the_published_pattern():
    w, model, params = setup()
    assert model.pattern == "SDWDSDWDSDFDGDXD" == adapter.pattern(TOY_PHI)
    assert adapter.pattern({**TOY_PHI, "num_hidden_layers": 32}) == (
        "SDWD" * 8 + "SD" + "FD" + "GDXD" * 7)
    init, _ = model.init(jax.random.key(0))
    assert jax.tree.structure(params) == jax.tree.structure(init)
    assert all(a.shape == b.shape and a.dtype == b.dtype
               for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(init)))
    assert "head" not in params and params["embed"] is w["embed"]  # tied: one table
    assert set(params["layer14"]["mixer"]) == {  # a cross layer: no K, no V
        "q", "out", "subln", "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"}
    assert "bias" in params["layer0"]["norm"] and "bias" in params["layer2"]["mixer"]["q"]
    assert params["layer0"]["mixer"]["A_log"].shape == (64, 4)
    # lambda_init follows the published layer's index, not the pattern's
    assert [model._mixer(k, i).lambda_init for i, k in enumerate(model.pattern) if k in "WFX"] == [
        pytest.approx(ref.lambda_init(i)) for i in (1, 3, 5, 7)]
    for bad in ({"tie_word_embeddings": False}, {"mlp_bias": True}):
        with pytest.raises(ValueError, match="tied head"):
            adapter.build_model({**TOY_PHI, **bad}, {})


def test_a_pattern_that_reads_what_nothing_made_is_refused():
    kw = dict(vocab_size=8, embed_dim=16, num_heads=4, head_dim=4, differential=True)
    with pytest.raises(ValueError, match="'G' reads"):
        HybridLM(pattern="GDSD", **kw)
    with pytest.raises(ValueError, match="'X' reads"):
        HybridLM(pattern="WDXD", **kw)
    with pytest.raises(ValueError, match="differential form only"):
        HybridLM(pattern="FDXD", **{**kw, "differential": False})
    with pytest.raises(ValueError, match="norm"):
        HybridLM(pattern="SD", norm="batch", **kw)
    assert HybridLM(pattern="SDFDGDXD", **kw).prefill_entries == 3
    # the models that were there prefill what they prefilled: their last entry reports routes
    assert HybridLM(vocab_size=8, pattern="MEMEM*EMEMEM*EME").prefill_entries == 16
    assert HybridLM(vocab_size=8, pattern="FDWEWEWEWEFEWE").prefill_entries == 14


# -------------------------------------------------------------------- serving


@pytest.mark.parametrize("n_prompt", [1, 17, 38, 49])
def test_prefill_then_decode_gives_the_reference_logits_at_every_position(n_prompt):
    """Window 8, chunks of 16, prompts up to six windows: a chunk continues the
    Mamba state and sees the previous chunk's rows through the ring, a padded
    tail lands in neither, the cross layers read rows that only prefill's
    shortened trunk wrote, and decode wraps the ring several times."""
    w, model, params = setup()
    assert served_error(TOY_PHI, w, model, params, tokens(n_prompt, n_prompt)) < 5e-5


def test_a_slot_taken_over_from_another_request_starts_clean():
    """A long request, then a short one in the same slot: the state is zeroed,
    the ring's rows past the new writer and the full cache's stale rows are not
    read. Without the reset (the control's plant) the second request is wrong."""
    w, model, params = setup()
    _, _, caches = serve(model, params, tokens(49, 1), 14)
    assert served_error(TOY_PHI, w, model, params, tokens(5, 2), caches=caches) < 5e-5
    undo = control_phi4flash.plant("no_state_reset")
    try:
        assert served_error(TOY_PHI, w, model, params, tokens(5, 2), caches=caches) > 1e-3
    finally:
        undo()


def test_a_pair_a_row_equals_a_head_a_row():
    """The cache as `[B, L * H/4, 1, 2D]` (a pair a row, flat) against
    `[B, L, H/2, D]`: the same rows in the same order, the same logits to float32
    rounding, and a cross layer owns no cache."""
    w, model, params = setup()
    heads = adapter.build_model(TOY_PHI, {"pair_rows": False})
    a, b = (m.init_decode_cache(3, 64, "f32") for m in (model, heads))
    assert a[10].k.shape == a[10].v.shape == (3, 64 * 2, 1, 8) and b[10].k.shape == (3, 64, 4, 4)
    assert a[2].k.shape == (3, 8 * 2, 1, 8) and b[2].v.shape == (3, 8, 4, 4)  # the rings
    assert a[0].ssm.shape == (3, 1, 4, 64) and a[0].conv.shape == (3, 3, 64)
    assert [c is None for c in a] == [k in "DGX" for k in model.pattern]
    prompt = tokens(38, 38)
    got_a, _, ca = serve(model, params, prompt, 12)
    got_b, _, cb = serve(heads, params, prompt, 12)
    np.testing.assert_allclose(np.asarray(got_a), np.asarray(got_b), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ca[10].k).reshape(2, 64, 4, 4), np.asarray(cb[10].k),
                               rtol=1e-6, atol=1e-7)


def test_the_kernel_reads_pair_rows_with_zero_padded_queries(monkeypatch, n_prompt=38):
    """At a head of 64 a pair is a 128-lane row: the step writes by one scatter
    and `ops/decode_attn.py` (interpreted) reads the full layer's cache, the ring
    and, for the cross layers, the full layer's cache again, with `[q1 | 0]` and
    `[0 | q2]` as its query rows; the einsum gives the same."""
    w, model, params = setup(WIDE)
    prompt = tokens(n_prompt, n_prompt)
    assert model.cache_forms(64, "f32") == (True, False)
    plain, seq, _ = serve(model, params, prompt, 4)
    want = ref.forward(WIDE, w, jnp.asarray(seq[:-1]))[len(prompt) - 1:]
    assert float(jnp.abs(plain - want).max()) < 2e-4
    monkeypatch.setattr(decode_attn, "kernel_interpret", lambda: True)
    assert model.cache_forms(64, "f32") == (True, True)
    calls = []
    real = decode_attn.decode_attn
    monkeypatch.setattr(decode_attn, "decode_attn", lambda q, k, v, pos, **kw: (
        calls.append((kw["name"], q.shape, k.shape, kw["block"])), real(q, k, v, pos, **kw))[1])
    got, _, _ = serve(model, params, prompt, 4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(plain), rtol=1e-4, atol=2e-5)
    assert calls[:4] == [("decode_attn_window", (2, 1, 8, 128), (2, 16 * 2, 1, 128), 16)] * 2 + [
        ("decode_attn", (2, 1, 8, 128), (2, 64 * 2, 1, 128), 64),
        ("decode_attn_shared", (2, 1, 8, 128), (2, 64 * 2, 1, 128), 64)]


def test_a_prefill_that_stops_behind_the_shared_cache_leaves_what_the_whole_trunk_leaves(
        monkeypatch):
    """Eleven of sixteen entries run; every cache, ring and state they leave
    equals what a prefill over all sixteen leaves, to the last bit."""
    w, model, params = setup()
    assert model.prefill_entries == 11 == 1 + model.pattern.index("F")

    def prefilled():
        caches = model.init_decode_cache(2, 64, "f32")
        for s0, n in ((0, 16), (16, 16), (32, 7)):
            chunk = np.zeros((1, 16), np.int32)
            chunk[0, :n] = tokens(39, 3)[s0:s0 + n]
            caches, routes = model.apply_prefill(params, caches, jnp.asarray(chunk),
                                                 jnp.asarray(1, jnp.int32), s0, jnp.asarray(n))
            assert routes.shape == (16, 0)
        return caches

    stopped = prefilled()
    monkeypatch.setattr(HybridLM, "prefill_entries", property(lambda self: len(self.pattern)))
    whole = prefilled()
    assert jax.tree.structure(stopped) == jax.tree.structure(whole)
    for a, b in zip(jax.tree.leaves(stopped), jax.tree.leaves(whole)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_shared_cache_is_written_once_a_step():
    """The decode step's scatters: K and V of the two rings and of the full
    layer, none for the cross layer (at a head of 64: one scatter a tensor)."""
    w, model, params = setup(WIDE)
    caches = model.init_decode_cache(2, 64, "f32")
    z = jnp.zeros((2,), jnp.int32)
    jaxpr = str(jax.make_jaxpr(model.apply_decode)(params, caches, z, z, z == 0))
    assert jaxpr.count(" scatter[") == 2 * (model.pattern.count("W") + 1) == 6
    _, new, _, _ = model.apply_decode(params, caches, z, z + 3, z == 0)
    assert [c is None for c in new] == [k in "DGX" for k in model.pattern]
    written = np.flatnonzero(np.abs(np.asarray(new[10].k)).sum(axis=(0, 2, 3)))
    assert written.tolist() == [6, 7]  # position 3: its two pair-rows
