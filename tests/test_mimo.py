"""The pattern model's window / full attention mixture (`tpudml.models.HybridLM`
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
- (in `tests/test_mimo_cache.py`: one file is one worker's work under
  `--dist loadfile`) the ring cache equals a `max_len` cache under the window
  mask, row for row, and the kernel (interpreted) reads both widths, the sink
  and the ring; the four held shares' parts add up to the uncut expert layer, in
  the program and the reference alike; the engine's `serve/dispatch` span says
  which forms the step runs and counts the live rows of each cache kind.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.drivers import mimo_adapter
from benchmarks.reference import mimo_v2 as ref
from benchmarks.tests.toy_mimo import TOY_MIMO
from tpudml.serve import cache as kv

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


# ------------------------------------------------------------- whole sequence


@pytest.mark.parametrize("cfg", [one_layer(0, 0), one_layer(1, 1), one_layer(0, 1), TOY_MIMO],
                         ids=["full+dense", "window+experts", "full+experts", "seven-layers"])
def test_apply_matches_the_reference(cfg):
    w, model, params = setup(cfg)
    tokens = _tokens(40)  # five windows long
    want = ref.forward(cfg, w, jnp.asarray(tokens))
    got, _ = model.apply(params, {}, jnp.asarray(tokens)[None])
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), rtol=1e-4, atol=2e-6)


def test_the_adapter_renames_every_leaf_and_builds_the_published_pattern():
    w, model, params = setup()
    assert model.pattern == "FDWEWEWEWEFEWE" == mimo_adapter.pattern(TOY_MIMO)
    init, _ = model.init(jax.random.key(0))
    assert jax.tree.structure(params) == jax.tree.structure(init)
    assert all(a.shape == b.shape and a.dtype == b.dtype
               for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(init)))
    assert params["layer3"]["mixer"]["experts"]["gate"] is w["layers.1.experts.gate"]
    assert "sink" in params["layer2"]["mixer"] and "sink" not in params["layer0"]["mixer"]
    assert model.held == (0, 4) and model.num_experts == 16 and model.route_width == 6 * 4
    assert ref.rotary_dim(TOY_MIMO) == 8 and ref.rotary_dim({"partial_rotary_factor": 0.334,
                                                             "head_dim": 192}) == 64
    with pytest.raises(ValueError, match="pattern"):
        mimo_adapter.build_model({**TOY_MIMO, "hybrid_override_pattern": "FD"}, {})


# -------------------------------------------------------------------- serving


def _serve(model, params, prompt, n_new, chunk=16, max_len=64, slot=1, slots=2):
    """Prefill ``prompt`` (all but its last token) in chunks with a padded tail,
    then decode ``n_new`` tokens feeding the reference-independent greedy
    choice back: logits at every decode position [n_new, V]."""
    caches = model.init_decode_cache(slots, max_len, "f32")
    p = len(prompt) - 1
    for s0 in range(0, p, chunk):
        n = min(chunk, p - s0)
        padded = np.zeros((1, chunk), np.int32)
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
    return jnp.stack(out), np.asarray(seq, np.int32)


def _served_error(cfg, w, model, params, prompt, n_new=12) -> float:
    got, seq = _serve(model, params, prompt, n_new)
    want = ref.forward(cfg, w, jnp.asarray(seq[:-1]))[len(prompt) - 1:]
    return float(jnp.abs(got - want).max())


@pytest.mark.parametrize("n_prompt", [1, 9, 17, 38, 49])
def test_prefill_then_decode_gives_the_reference_logits_at_every_position(n_prompt):
    """Window 8, chunks of 16, prompts up to six windows: a chunk's first
    queries see the previous chunk's last seven rows through the ring, a padded
    tail never lands in it, and decode wraps it several times."""
    w, model, params = setup()
    assert _served_error(TOY_MIMO, w, model, params, _tokens(n_prompt, n_prompt)) < 2e-5


FAULTS = {
    "sink": dict(window_sink=False),
    "window_edge": dict(window=7),
    "rotary_width": dict(rotary_dim=24),
    "value_scale": dict(value_scale=1.0),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_in_one_mechanism_shows(fault):
    """The program built with one mechanism wrong (as the cell's controls build
    it) no longer gives the reference's logits, in `apply` and through the
    caches; the sound program's error is a thousand times smaller."""
    w, model, params = setup(**FAULTS[fault])
    tokens = _tokens(40)
    want = ref.forward(TOY_MIMO, w, jnp.asarray(tokens))
    got, _ = model.apply(params, {}, jnp.asarray(tokens)[None])
    assert float(jnp.abs(got[0] - want).max()) > 1e-3
    assert _served_error(TOY_MIMO, w, model, params, _tokens(38, 38)) > 1e-3


def test_the_head_widths_are_the_caches_and_the_projections():
    """K 24 and V 16 wide: the caches, the projections and the out projection
    take their own widths, and a model told the wrong value width cannot even
    hold the reference's weights."""
    w, model, params = setup()
    caches = model.init_decode_cache(3, 64, "f32")
    full, ring = caches[0], caches[2]
    assert full.k.shape == (3, 64, 2, 24) and full.v.shape == (3, 64, 2, 16)
    assert ring.k.shape == (3, 8, 4, 24) and ring.v.shape == (3, 8, 4, 16)
    assert caches[1] is None and caches[3] is None
    mixer = params["layer2"]["mixer"]
    assert mixer["k"]["kernel"].shape == (48, 4 * 24) and mixer["v"]["kernel"].shape == (48, 4 * 16)
    assert mixer["out"]["kernel"].shape == (8 * 16, 48)
    wrong = mimo_adapter.build_model({**TOY_MIMO, "v_head_dim": 24}, {})
    with pytest.raises((TypeError, ValueError)):
        wrong.apply(params, {}, jnp.asarray(_tokens(9))[None])
    # a key wider than a 128-lane tile is stored in whole tiles, the rest zero
    assert [kv.stored_width(d) for d in (16, 64, 96, 128, 192, 256)] == [16, 64, 96, 128, 256, 256]
    assert kv.fit_width(jnp.ones((2, 3, 192)), 256)[..., 192:].sum() == 0


def test_a_ring_that_forgets_the_previous_chunk_shows():
    """The control tool's plant: a chunk that sees nothing of the ring loses
    the previous chunk's last rows."""
    from benchmarks.tools import control_mimo

    w, model, params = setup()
    undo = control_mimo.plant("ring_forgets_chunk")
    try:
        assert _served_error(TOY_MIMO, w, model, params, _tokens(38, 38)) > 1e-3
        assert _served_error(TOY_MIMO, w, model, params, _tokens(9, 9)) < 2e-5  # one chunk
    finally:
        undo()
    assert _served_error(TOY_MIMO, w, model, params, _tokens(38, 38)) < 2e-5


def test_a_padded_tail_written_into_the_ring_would_show(monkeypatch):
    """`write_ring_chunk` told the whole padded chunk is real lays the tail over
    rows that still count."""
    w, model, params = setup()
    real = kv.write_ring_chunk
    monkeypatch.setattr(kv, "write_ring_chunk",
                        lambda cache, k, v, slot, start, n_real: real(
                            cache, k, v, slot, start, k.shape[1]))
    assert _served_error(TOY_MIMO, w, model, params, _tokens(38, 38)) > 1e-3  # 37 = 2 x 16 + 5
