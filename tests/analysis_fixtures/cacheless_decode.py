"""J110 firing fixture: the decode strategy the KV cache exists to kill.

Re-runs the full forward over the whole history and keeps the last
logits row. It carries the serving decode marker, and the [T, T] softmax
inside it is precisely what rule J110 reports. One compile per history
length, too (tokens [B, T] is shape-polymorphic in T) — recompile churn
the slot engine never pays. Not part of ``tpudml.serve``: nothing serves
with it.
"""

import jax
import jax.numpy as jnp

from tpudml.serve import SERVE_DECODE_MARKER


def make_cacheless_decode_step(model):
    def _serve_decode_step(params, tokens):
        logits, _ = model.apply(params, {}, tokens)
        return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)

    assert _serve_decode_step.__name__ == SERVE_DECODE_MARKER
    inner = jax.jit(_serve_decode_step)
    return jax.jit(lambda params, tokens: inner(params, tokens))
