"""The plain reference against the program's plainest path at toy size:
`TransformerLM(impl="full")` in float32, the same seeded weights."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.drivers import lm_adapter
from benchmarks.reference import gpt2
from benchmarks.tests import toy


@pytest.mark.parametrize("config", [toy.TOY_GPT2, toy.TOY_BIGCODE],
                         ids=["mha", "multi_query"])
def test_reference_forward_matches_the_plain_model(config):
    weights = gpt2.init_weights(config, gpt2.seed_key(11))
    model = lm_adapter.build_model(config, {"impl": "full"})
    params = lm_adapter.to_program(weights, config["n_layer"])
    tokens = jax.random.randint(jax.random.key(1), (2, 48), 0, config["vocab_size"])
    logits, _ = model.apply(params, {}, tokens)
    x = gpt2.embed(weights, tokens)
    for i in range(config["n_layer"]):
        x = gpt2.block(config, gpt2.layer_leaves(weights, i), x)
    ref = gpt2.head_logits(config, weights, x)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref), atol=2e-5)
    # attention in blocks of query rows is the same mathematics
    xb = gpt2.embed(weights, tokens)
    for i in range(config["n_layer"]):
        xb = gpt2.block(config, gpt2.layer_leaves(weights, i), xb, q_block=16)
    np.testing.assert_allclose(np.asarray(xb), np.asarray(x), atol=2e-5)
    rows = gpt2.served_rows_logits(config, weights, tokens[0], 40, 8, q_block=16)
    np.testing.assert_allclose(np.asarray(rows), np.asarray(ref[0, 40:48]), atol=2e-5)


def test_adapter_round_trip_and_leaf_count():
    weights = gpt2.init_weights(toy.TOY_GPT2, gpt2.seed_key(3))
    tree = lm_adapter.to_program(weights, 2)
    assert len(jax.tree.leaves(tree)) == len(weights)
    back = lm_adapter.from_program(tree, 2)
    assert all(back[k] is weights[k] for k in weights)
    shapes = jax.eval_shape(
        lm_adapter.build_model(toy.TOY_GPT2, {"impl": "full"}).init, jax.random.key(0))[0]
    assert jax.tree.map(lambda a: a.shape, tree) == jax.tree.map(lambda a: a.shape, shapes)


def test_seeded_weights_repeat_and_take_wide_seeds():
    a = gpt2.init_weights(toy.TOY_GPT2, gpt2.seed_key(2 ** 31 + 9))
    b = gpt2.init_weights(toy.TOY_GPT2, gpt2.seed_key(2 ** 31 + 9))
    c = gpt2.init_weights(toy.TOY_GPT2, gpt2.seed_key(9))
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["wte"], c["wte"])
    assert abs(float(jnp.mean(a["h.0.ln_1.g"])) - 1.0) < 0.02


def test_long_sequences_in_blocks_are_the_same_mathematics(monkeypatch):
    cfg = toy.TOY_BIGCODE
    weights = gpt2.init_weights(cfg, gpt2.seed_key(5))
    tokens = jax.random.randint(jax.random.key(2), (1, 64), 0, cfg["vocab_size"])
    targets = jnp.roll(tokens, -1, axis=1)

    def loss_and_grad(remat):
        return jax.value_and_grad(lambda w: jnp.sum(
            gpt2.token_losses(cfg, w, tokens, targets, remat=remat)))(weights)

    whole, g_whole = loss_and_grad(False)
    monkeypatch.setattr(gpt2, "LONG", 16)  # 64 positions now count as long
    monkeypatch.setattr(gpt2, "block", lambda cfg_, lw, x, q_block=None, remat=False,
                        _b=gpt2.block: _b(cfg_, lw, x, 16 if q_block else None, remat))
    blocks, g_blocks = loss_and_grad(True)
    np.testing.assert_allclose(float(blocks), float(whole), rtol=1e-6)
    for k in g_whole:
        np.testing.assert_allclose(np.asarray(g_blocks[k]), np.asarray(g_whole[k]),
                                   atol=2e-5 * float(jnp.max(jnp.abs(g_whole[k]))) + 1e-7)  # k.b: zero by the maths
