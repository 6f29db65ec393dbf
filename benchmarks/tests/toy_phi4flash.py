"""Toy `phi4flash` configuration and cell for the CPU tests: eight published
layers (two window layers, the publishing Mamba and the full layer, a memory unit
and cross layers behind them) at sizes a test can hold, every ratio kept: twice
as many query heads as K/V heads, four query heads a K/V pair, a window shorter
than a chunk, an expansion of two; the real `serve_phi4flash` driver over it; and
what the program's tests share: the reference's weights as the program's tree, and
a prefill-then-decode loop over the model's own serving paths."""

from __future__ import annotations

import copy
import json

import jax.numpy as jnp
import numpy as np

from benchmarks import cells
from benchmarks.drivers import phi4flash_adapter
from benchmarks.reference import phi4flash as ref

TOY_PHI = {
    "hidden_size": 32, "intermediate_size": 64, "layer_norm_eps": 1e-5, "mb_per_layer": 2,
    "num_attention_heads": 8, "num_hidden_layers": 8, "num_key_value_heads": 4,
    "sliding_window": 8, "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
    "vocab_size": 96,
    "assumed": {"mamba_d_state": 4, "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 2},
}

CELL = "phi-4-mini-flash-reasoning.serve-reasoning-4k"


def serve_cell(config: dict = TOY_PHI) -> cells.Cell:
    with open(cells.BENCH / "workloads" / f"{CELL}.json") as f:
        spec = copy.deepcopy(json.load(f))
    spec["engine"]["serve_config"].update(slots=4, max_len=64, prefill_chunk=16,
                                          cache_kind="f32")
    spec["model"].update(param_dtype="float32")
    spec["warmup"] = [{"prompt_len": 49, "max_new_tokens": 2}]
    spec["ramp_s"] = 0.5
    spec["trace"] = {"start_s": 0.0, "seconds": 60.0}
    spec["check"]["pad_to"] = [64]
    # float32 against float32: exact ties aside, the sound engine's gaps are 0
    spec["check"]["limits"] = {"served_token_gap": 1e-4, "served_mean_gap": 1e-6}
    return cells.Cell(
        name="toy.serve-phi4flash", chips=1, config=copy.deepcopy(config),
        traffic={"generator": "requests", "rate_per_s": 20.0,
                 "prompt_len": {"median": 12, "sigma": 0.8, "min": 1, "max": 48},
                 "output_len": {"median": 6, "sigma": 0.5, "min": 2, "max": 12}},
        spec=spec,
        end_to_end=[{"name": n, "unit": u} for n, u in (
            ("serve.tokens_per_s", "tokens/s"), ("setup_s", "s"))],
        per_layer=[])


# ------------------------------------------------- shared by the program's tests


def setup(cfg=TOY_PHI, seed=5, **options):
    w = ref.init_weights(cfg, ref.seed_key(seed))
    return w, phi4flash_adapter.build_model(cfg, options), phi4flash_adapter.to_program(w, cfg)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, TOY_PHI["vocab_size"], n).astype(np.int32)


def serve(model, params, prompt, n_new, chunk=16, max_len=64, slot=1, slots=2, caches=None):
    """Prefill ``prompt`` (all but its last token) in chunks with a padded tail
    into ``slot`` (its state zeroed first, as admission does), then decode
    ``n_new`` tokens feeding the greedy choice back: (logits at every decode
    position [n_new, V], the sequence, the caches)."""
    if caches is None:
        caches = model.init_decode_cache(slots, max_len, "f32")
    caches = model.reset_slot(caches, jnp.asarray(slot, jnp.int32))
    p = len(prompt) - 1
    for s0 in range(0, p, chunk):
        n = min(chunk, p - s0)
        padded = np.zeros((1, chunk), np.int32)
        padded[0, :n] = prompt[s0:s0 + n]
        caches, _ = model.apply_prefill(params, caches, jnp.asarray(padded),
                                        jnp.asarray(slot, jnp.int32), s0, jnp.asarray(n))
    out, seq = [], list(prompt)
    for t in range(p, p + n_new):
        step_tokens = jnp.zeros((slots,), jnp.int32).at[slot].set(seq[t])
        pos = jnp.zeros((slots,), jnp.int32).at[slot].set(t)
        active = jnp.zeros((slots,), bool).at[slot].set(True)
        logits, caches, _, _ = model.apply_decode(params, caches, step_tokens, pos, active)
        out.append(logits[slot])
        seq.append(int(jnp.argmax(logits[slot])))
    return jnp.stack(out), np.asarray(seq, np.int32), caches


def served_error(cfg, w, model, params, prompt, n_new=12, **kw) -> float:
    got, seq, _ = serve(model, params, prompt, n_new, **kw)
    want = ref.forward(cfg, w, jnp.asarray(seq[:-1]))[len(prompt) - 1:]
    return float(jnp.abs(got - want).max())
