"""The second half of `tests/test_phi4flash.py` (one file is one worker's work
under `--dist loadfile`): each of the cell's faults planted in the program shows
against the reference; `Mamba1` alone over padded chunks and idle slots; the
engine end to end with its counters of the shared cache; the caches at the
published sizes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.drivers import phi4flash_adapter as adapter
from benchmarks.reference import phi4flash as ref
from benchmarks.tests.toy_phi4flash import TOY_PHI, served_error, setup, tokens
from benchmarks.tools import control_phi4flash
from tpudml.nn.mamba import Mamba1
from tpudml.obs.tracer import Tracer, use_tracer
from tpudml.ops import decode_attn
from tpudml.serve.engine import ServeConfig, ServingEngine
from tpudml.serve.load import Request


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


# --------------------------------------------------------------------- faults

FAULTS = {
    "lambda_of_layer0": {"plant": "lambda_of_layer0"},
    "no_subln": {"plant": "no_subln"},
    "window_edge": {"model": {"window": 7}},
    "memory_after_gate": {"plant": "memory_after_gate"},
    "cross_reads_own_kv": {"plant": "cross_reads_own_kv"},
    "prefill_skips_kv": {"plant": "prefill_skips_kv"},
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_in_one_mechanism_shows(fault):
    """The program built with one mechanism wrong (as the cell's controls build
    it) no longer gives the reference's logits through the caches; the sound
    program's error on this prompt is a hundred times smaller (the test above)."""
    undo = control_phi4flash.plant(FAULTS[fault].get("plant"))
    try:
        w, model, params = setup(**FAULTS[fault].get("model", {}))
        assert served_error(TOY_PHI, w, model, params, tokens(38, 38)) > 1e-3
    finally:
        undo()


# ----------------------------------------------------------------- the mixer


def test_mamba1_chunks_with_a_padded_tail_and_idle_slots():
    """`Mamba1` alone: `apply` is the reference's mixer; two prefill chunks,
    the second with a padded tail, leave the state and the window of the real
    tokens and give their outputs; a decode step moves active slots only."""
    cfg = {**TOY_PHI, "num_hidden_layers": 4}
    w = ref.init_weights(cfg, ref.seed_key(3))
    lw = {k: a for k, a in ref.layer_leaves(w, 0).items()}
    p = adapter.to_program(w, cfg)["layer0"]["mixer"]
    mixer = Mamba1(32, 64, 4, 2, 4)
    u = jax.random.normal(jax.random.key(1), (21, 32))
    want, want_m = ref.mamba_mixer(cfg, lw, u)
    got, m = mixer.forward(p, u[None])
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(m[0]), np.asarray(want_m), rtol=1e-4, atol=1e-6)
    cache = adapter.build_model(cfg, {}).init_decode_cache(3, 32, "f32")[0]
    slot = jnp.asarray(2, jnp.int32)
    out1, _, cache = mixer.apply_prefill(p, cache, u[None, :16], slot, jnp.asarray(16))
    tail = jnp.concatenate([u[16:20], 7.0 * jnp.ones((12, 32))])[None]
    out2, _, cache = mixer.apply_prefill(p, cache, tail, slot, jnp.asarray(4))
    np.testing.assert_allclose(np.asarray(jnp.concatenate([out1[0], out2[0, :4]])),
                               np.asarray(want[:20]), rtol=1e-4, atol=1e-6)
    assert float(jnp.abs(cache.ssm[:2]).max()) == 0.0  # the other slots were not touched
    step, _, new = mixer.apply_decode(p, cache, jnp.broadcast_to(u[20], (3, 1, 32)),
                                      jnp.asarray([True, False, True]))
    np.testing.assert_allclose(np.asarray(step[2, 0]), np.asarray(want[20]), rtol=1e-4, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(new.ssm[1]), np.asarray(cache.ssm[1]))
    np.testing.assert_array_equal(np.asarray(new.conv[1]), np.asarray(cache.conv[1]))
    assert float(jnp.abs(new.ssm[0]).max()) > 0.0


# -------------------------------------------------------------------- engine


def test_engine_serves_the_reference_and_counts_what_reads_the_shared_cache():
    """Five requests through three slots (two are taken over): every served
    token is the reference's greedy choice. `serve/dispatch` counts the full
    cache's live rows once (`rows_full`) and once a reader (`rows_read_full`:
    the full layer and the cross layer) and the active slots' recurrent
    state; `serve/admit` says how much of the trunk a prefill chunk runs."""
    w, model, params = setup()
    engine = ServingEngine(model, params,
                           ServeConfig(slots=3, max_len=64, prefill_chunk=16, cache_kind="f32"))
    reqs = [Request(rid=i, prompt=tokens(n, 100 + i), max_new_tokens=m, arrival_time=0.0)
            for i, (n, m) in enumerate([(37, 10), (1, 12), (20, 9), (48, 14), (17, 5)])]
    tracer = Tracer()
    with use_tracer(tracer):
        report = engine.run(reqs)
    for r in reqs:
        st = report.requests[r.rid]
        seq = np.concatenate([r.prompt, np.asarray(st.tokens[:-1], np.int32)])
        logits = ref.served_rows_logits(TOY_PHI, w, jnp.asarray(seq), len(r.prompt) - 1,
                                        len(st.tokens))
        assert st.finished is not None and st.routes == []
        assert np.asarray(jnp.argmax(logits, axis=-1)).tolist() == st.tokens
    steps = [e.args for e in tracer.events if e.cat == "serve" and e.name == "dispatch"]
    slot_state = 3 * (4 * 64 * 4 + 3 * 64 * 4)  # three S layers: [N, E] and K - 1 rows, float32
    assert all(s["rows_full"] == s["rows"] + s["active"] for s in steps)
    assert all(s["rows_read_full"] == 2 * s["rows_full"] for s in steps)
    assert all(s["state_bytes"] == s["active"] * slot_state for s in steps)
    assert all(s["rows_window"] <= 2 * 8 * s["active"] for s in steps)
    assert all(s["cache_bytes_full"] == 3 * 64 * 4 * 4 * 2 * 4
               and s["cache_bytes_window"] == 2 * 3 * 8 * 4 * 4 * 2 * 4 for s in steps)
    admits = [e.args for e in tracer.events if e.cat == "serve" and e.name == "admit"]
    assert len(admits) == 5 and all(a["trunk_prefilled"] == 11 and a["state_reset"] == 1
                                    for a in admits)


def test_the_published_sizes_give_the_caches_the_issue_counts(monkeypatch):
    """At the published widths (shapes only): one full cache of 64 x 4096 x 10 pair-rows,
    eight rings of 512, nine states with the channels in the lanes; both fast
    paths hold; a step at 1,100 rows a slot reads the full cache eight times."""
    cfg = {**TOY_PHI, "hidden_size": 2560, "intermediate_size": 10240, "num_attention_heads": 40,
           "num_key_value_heads": 20, "num_hidden_layers": 32, "sliding_window": 512,
           "vocab_size": 200064, "assumed": {"mamba_d_state": 16, "mamba_d_conv": 4,
                                             "mamba_expand": 2, "mamba_dt_rank": 160}}
    model = adapter.build_model(cfg, {"param_dtype": "bfloat16"})
    assert model.prefill_entries == 35 and len(model.pattern) == 64
    caches = jax.eval_shape(lambda: model.init_decode_cache(64, 4096, "bf16"))
    assert sum(c is not None for c in caches) == 9 + 8 + 1
    assert caches[34].k.shape == caches[34].v.shape == (64, 4096 * 10, 1, 128)
    assert caches[2].k.shape == (64, 512 * 10, 1, 128)
    assert caches[0].ssm.shape == (64, 1, 16, 5120) and caches[0].ssm.dtype == jnp.float32
    assert caches[0].conv.shape == (64, 3, 5120) and caches[0].conv.dtype == jnp.bfloat16
    assert model.cache_bytes(caches) == {"cache_bytes_full": 64 * 4096 * 5120,
                                         "cache_bytes_window": 8 * 64 * 512 * 5120}
    assert model.cache_forms(4096, "bf16") == (True, False)
    monkeypatch.setattr(decode_attn, "kernel_interpret", lambda: False)
    assert model.cache_forms(4096, "bf16") == (True, True)
    assert model.cache_forms(4096, "int8_sim") == (True, False)
    assert adapter.build_model(cfg, {"pair_rows": False}).cache_forms(4096, "bf16") == (
        False, False)
    live = model.live_rows(np.full(64, 1099), 4096)
    assert live == {"rows_full": 64 * 1100, "rows_read_full": 8 * 64 * 1100,
                    "rows_window": 8 * 64 * 512,
                    "state_bytes": 64 * 9 * (16 * 5120 * 4 + 3 * 5120 * 2)}

