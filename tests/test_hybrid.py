"""The pattern model (`tpudml.models.HybridLM`: Mamba-2, sigmoid-routed
experts with a shared expert and a held share, grouped-query attention with
an explicit head size) against its plain reference
(`benchmarks/reference/nemotron_h.py`, the yardstick's: one text serves the
tests and `correct`), at a small size in float32.

Load-bearing properties:

- `apply` equals the reference's forward for each layer kind and the whole
  model; the chunked scan equals the step-by-step recurrence for any chunk;
- prefill in chunks with a padded tail, then decode through the cache, gives
  the reference's logits at every position — also in a slot taken over from
  a finished request and for a one-token prompt (no prefill chunk at all);
- the parts two shares of the experts give, the shared expert counted once,
  add up to the uncut layer, in the program and the reference alike;
- slots that are not active reach no expert, move no counter and keep their
  state;
- an engine run is deterministic and its `serve/commit` counters add up; the
  routes it keeps for a request are the reference's own choices at every
  position, prompt and served;
- every lever not built for a pattern model is a capability-row rejection.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import nemotron_h as ref
from tpudml.capabilities import TABLE, CompositionError
from tpudml.models import HybridLM
from tpudml.nn import GatedGroupRMSNorm, MultiHeadAttention, RMSNorm, SigmoidMoE
from tpudml.obs.tracer import Tracer, use_tracer
from tpudml.serve.engine import ServeConfig, ServingEngine
from tpudml.serve.load import Request

CFG = {
    "hidden_size": 48, "norm_eps": 1e-5, "hybrid_override_pattern": "ME*M",
    "vocab_size": 96, "mamba_num_heads": 8, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "n_routed_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "moe_intermediate_size": 24,
    "moe_shared_expert_intermediate_size": 40,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
}
_M = {"in_proj.w": ("in_proj", "kernel"), "conv.w": ("conv", "kernel"),
      "conv.b": ("conv", "bias"), "dt_bias": ("dt_bias",), "A_log": ("A_log",),
      "D": ("D",), "gate_norm.w": ("norm", "scale"), "out_proj.w": ("out_proj", "kernel")}
_E = {"router.w": ("router", "kernel"), "router.bias": ("router", "bias"),
      "experts.up": ("experts", "up"), "experts.down": ("experts", "down"),
      "shared.up": ("shared", "up"), "shared.down": ("shared", "down")}
_A = {"q.w": ("q", "kernel"), "k.w": ("k", "kernel"), "v.w": ("v", "kernel"),
      "o.w": ("out", "kernel")}


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def model_of(cfg, **kw):
    return HybridLM(
        vocab_size=cfg["vocab_size"], pattern=cfg["hybrid_override_pattern"],
        embed_dim=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        mamba_heads=cfg["mamba_num_heads"], mamba_head_dim=cfg["mamba_head_dim"],
        n_groups=cfg["n_groups"], state_size=cfg["ssm_state_size"],
        conv_kernel=cfg["conv_kernel"], chunk_size=cfg["chunk_size"],
        num_experts=ref.router_width(cfg), top_k=cfg["num_experts_per_tok"],
        expert_dim=cfg["moe_intermediate_size"],
        shared_dim=cfg["moe_shared_expert_intermediate_size"],
        routed_scale=cfg["routed_scaling_factor"], norm_topk=cfg["norm_topk_prob"],
        eps=cfg["norm_eps"], **kw)


def to_program(w, cfg):
    """The reference's flat leaves as the program's tree: renaming only."""
    tree = {"embed": w["embed"], "norm_f": {"scale": w["norm_f.w"]},
            "head": {"kernel": w["lm_head.w"]}}
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        mixer: dict = {}
        for leaf, path in {"M": _M, "E": _E, "*": _A}[kind].items():
            node = mixer
            for key in path[:-1]:
                node = node.setdefault(key, {})
            node[path[-1]] = w[f"layers.{i}.{leaf}"]
        tree[f"layer{i}"] = {"norm": {"scale": w[f"layers.{i}.norm.w"]}, "mixer": mixer}
    return tree


def setup(pattern="ME*M", seed=5, **cfg_kw):
    cfg = {**CFG, "hybrid_override_pattern": pattern, **cfg_kw}
    w = ref.init_weights(cfg, ref.seed_key(seed))
    return cfg, w, model_of(cfg), to_program(w, cfg)


# ------------------------------------------------------------- whole sequence


@pytest.mark.parametrize("pattern", ["M", "E", "*", "ME*M", "MEMEM*E"])
def test_apply_matches_the_reference(pattern):
    cfg, w, model, params = setup(pattern)
    init, _ = model.init(jax.random.key(0))
    assert jax.tree.structure(init) == jax.tree.structure(params)
    assert all(a.shape == b.shape and a.dtype == b.dtype
               for a, b in zip(jax.tree.leaves(init), jax.tree.leaves(params)))
    tokens = jax.random.randint(jax.random.key(1), (2, 21), 0, cfg["vocab_size"])
    logits, _ = model.apply(params, {}, tokens)
    want = jnp.stack([ref.forward(cfg, w, t) for t in tokens])
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("chunk", [1, 4, 8, 64])
def test_chunked_scan_equals_the_recurrence(chunk):
    """Any chunk size, dividing the 21 tokens or not, longer than them or not."""
    cfg, w, model, params = setup("M", chunk_size=chunk)
    u = jax.random.normal(jax.random.key(2), (1, 21, cfg["hidden_size"]))
    got, _ = model._mixer("M").apply(params["layer0"]["mixer"], {}, u)
    lw = {k: a.astype(jnp.float32) for k, a in ref.layer_leaves(w, 0).items()}
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref.mamba_mixer(cfg, lw, u[0])),
                               rtol=1e-5, atol=1e-6)


def test_norms_follow_their_equations():
    x = jax.random.normal(jax.random.key(3), (3, 5, 24))
    z = jax.random.normal(jax.random.key(4), (3, 5, 24))
    scale = 1.0 + 0.1 * jax.random.normal(jax.random.key(5), (24,))
    got, _ = RMSNorm(24, 1e-5).apply({"scale": scale}, {}, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.rms_norm(x, scale, 1e-5)),
                               rtol=1e-6)
    got, _ = GatedGroupRMSNorm(24, 3, 1e-5).apply({"scale": scale}, {}, x, gate=z)
    y = (x * jax.nn.silu(z)).reshape(3, 5, 3, 8)
    want = ref.rms_norm(y, scale.reshape(3, 8), 1e-5).reshape(3, 5, 24)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    with pytest.raises(ValueError):
        GatedGroupRMSNorm(24, 5)


def test_attention_takes_an_explicit_head_size_and_no_bias():
    attn = MultiHeadAttention(48, 4, causal=True, num_kv_heads=2, head_dim=16, use_bias=False)
    params, _ = attn.init(jax.random.key(0))
    assert {k: {n: a.shape for n, a in v.items()} for k, v in params.items()} == {
        "q": {"kernel": (48, 64)}, "k": {"kernel": (48, 32)}, "v": {"kernel": (48, 32)},
        "out": {"kernel": (64, 48)}}
    derived = MultiHeadAttention(48, 4)
    assert set(derived.init(jax.random.key(0))[0]["q"]) == {"kernel", "bias"}
    with pytest.raises(ValueError):
        MultiHeadAttention(50, 4)


# ------------------------------------------------------------------- serving


def serve_logits(model, params, caches, slot, seq, n_prompt, slots, chunk=8):
    """What the engine does for one request, by hand, keeping the logits:
    reset the slot, prefill seq[:n_prompt - 1] in padded chunks, then decode
    the rest a token at a time. Returns (logits at positions n_prompt - 1..,
    caches)."""
    prefill = jax.jit(model.apply_prefill, static_argnums=(4,))
    decode = jax.jit(model.apply_decode)
    slot_j = jnp.asarray(slot, jnp.int32)
    caches = model.reset_slot(caches, slot_j)
    p = n_prompt - 1
    for s0 in range(0, p, chunk):
        n = min(chunk, p - s0)
        padded = np.zeros((1, chunk), np.int32)
        padded[0, :n] = seq[s0:s0 + n]
        caches, _ = prefill(params, caches, jnp.asarray(padded), slot_j, s0,
                            jnp.asarray(n, jnp.int32))
    active = jnp.arange(slots) == slot
    rows = []
    for pos in range(p, len(seq)):
        tokens = jnp.full((slots,), 7, jnp.int32).at[slot].set(int(seq[pos]))
        logits, caches, _, _ = decode(
            params, caches, tokens, jnp.full((slots,), pos, jnp.int32), active)
        rows.append(logits[slot])
    return jnp.stack(rows), caches


@pytest.mark.parametrize("n_prompt", [14, 17, 1], ids=["padded_tail", "whole_chunks", "one_token"])
def test_prefill_then_decode_gives_the_reference_logits_at_every_position(n_prompt):
    cfg, w, model, params = setup()
    seq = np.asarray(jax.random.randint(jax.random.key(6), (24,), 0, cfg["vocab_size"]))
    caches = model.init_decode_cache(3, 32, "f32")
    got, _ = serve_logits(model, params, caches, 1, seq, n_prompt, slots=3)
    want = ref.forward(cfg, w, jnp.asarray(seq))[n_prompt - 1:]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n_prompt", [11, 1], ids=["prefilled", "one_token"])
def test_a_slot_taken_over_from_a_finished_request_starts_clean(n_prompt):
    cfg, w, model, params = setup()
    first = np.asarray(jax.random.randint(jax.random.key(7), (20,), 0, cfg["vocab_size"]))
    second = np.asarray(jax.random.randint(jax.random.key(8), (15,), 0, cfg["vocab_size"]))
    caches = model.init_decode_cache(2, 32, "f32")
    _, caches = serve_logits(model, params, caches, 0, first, 13, slots=2)
    got, _ = serve_logits(model, params, caches, 0, second, n_prompt, slots=2)
    want = ref.forward(cfg, w, jnp.asarray(second))[n_prompt - 1:]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


def test_a_skipped_reset_or_an_unmasked_tail_would_show():
    """The two faults the engine's mechanism exists to prevent are visible to
    the comparison above: state left by the last tenant, and padded tokens
    advancing the state."""
    cfg, w, model, params = setup()
    seq = np.asarray(jax.random.randint(jax.random.key(9), (12,), 0, cfg["vocab_size"]))
    want = np.asarray(ref.forward(cfg, w, jnp.asarray(seq)))
    caches = model.init_decode_cache(1, 32, "f32")
    _, dirty = serve_logits(model, params, caches, 0, seq[::-1].copy(), 9, slots=1)
    kept = model.reset_slot  # a model whose reset does nothing
    object.__setattr__(model, "reset_slot", lambda caches, slot: caches)
    try:
        got, _ = serve_logits(model, params, dirty, 0, seq, 1, slots=1)
    finally:
        object.__setattr__(model, "reset_slot", kept)
    assert np.abs(np.asarray(got) - want).max() > 1e-3
    # a padded tail counted as real: 8 tokens "real" where 5 are
    slot = jnp.asarray(0, jnp.int32)
    padded = np.zeros((1, 8), np.int32)
    padded[0, :5] = seq[:5]
    fresh = model.init_decode_cache(1, 32, "f32")
    right, _ = model.apply_prefill(params, fresh, jnp.asarray(padded), slot, 0, jnp.asarray(5))
    wrong, _ = model.apply_prefill(params, fresh, jnp.asarray(padded), slot, 0, jnp.asarray(8))
    assert float(jnp.abs(right[0].ssm - wrong[0].ssm).max()) > 1e-4
    assert float(jnp.abs(right[0].conv - wrong[0].conv).max()) > 1e-4


# -------------------------------------------------------------------- experts


def _moe(cfg, held=None, **kw):
    return SigmoidMoE(cfg["hidden_size"], ref.router_width(cfg), cfg["num_experts_per_tok"],
                      cfg["moe_intermediate_size"], cfg["moe_shared_expert_intermediate_size"],
                      cfg["routed_scaling_factor"], cfg["norm_topk_prob"], held, **kw)


def _share(params, first, count):
    ex = params["experts"]
    return {**params, "experts": {k: ex[k][first:first + count] for k in ex}}


@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_shares_add_up_to_the_uncut_layer(side):
    """Experts 0-3 and 4-7, the shared expert counted once."""
    cfg, w, _, params = setup("E")
    u = jax.random.normal(jax.random.key(10), (19, cfg["hidden_size"]))
    p = params["layer0"]["mixer"]
    lw = {k: a.astype(jnp.float32) for k, a in ref.layer_leaves(w, 0).items()}
    if side == "program":
        whole = _moe(cfg).forward(p, u)[0]
        low = _moe(cfg, (0, 4)).forward(_share(p, 0, 4), u)[0]
        high = _moe(cfg, (4, 4)).forward(_share(p, 4, 4), u)[0]
        shared = jnp.square(jax.nn.relu(u @ p["shared"]["up"])) @ p["shared"]["down"]
    else:
        whole = ref.moe_mixer(cfg, lw, u)
        low = ref.moe_mixer(cfg, lw, u, held=(0, 4))
        high = ref.moe_mixer(cfg, lw, u, held=(4, 4))
        shared = ref.moe_mixer(cfg, lw, u, held=(0, 0))
    np.testing.assert_allclose(np.asarray(low + high - shared), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(low - whole).max()) > 1e-3  # a share is not the layer


def test_a_held_share_matches_the_reference_given_the_same_share():
    cfg, w, _, params = setup("E")
    u = jax.random.normal(jax.random.key(11), (13, cfg["hidden_size"]))
    lw = {k: a.astype(jnp.float32) for k, a in ref.layer_leaves(w, 0).items()}
    got, counts = _moe(cfg, (2, 3)).forward(_share(params["layer0"]["mixer"], 2, 3), u)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.moe_mixer(cfg, lw, u, held=(2, 3))),
                               rtol=1e-5, atol=1e-6)
    assert int(counts["routed"]) == 13 * 2 and 0 < int(counts["held"]) < 13 * 2
    assert int(counts["touched"]) <= 3 and int(counts["load_max"]) <= 13
    with pytest.raises(ValueError):
        _moe(cfg, (6, 3))


def test_inactive_slots_reach_no_expert_and_keep_their_state():
    cfg, _, model, params = setup(n_routed_experts=8)
    slots = 4
    caches = model.init_decode_cache(slots, 16, "f32")
    tokens = jnp.asarray([3, 9, 27, 81], jnp.int32)
    pos = jnp.zeros((slots,), jnp.int32)
    active = jnp.asarray([True, False, True, False])
    logits, new, counters, routes = model.apply_decode(params, caches, tokens, pos, active)
    assert routes.shape == (slots, model.route_width) == (slots, 2) and routes.dtype == jnp.int32
    # other tokens in the inactive slots: the same logits for the active ones,
    # the same counters
    other = tokens.at[1].set(50).at[3].set(60)
    logits2, _, counters2, routes2 = model.apply_decode(params, caches, other, pos, active)
    np.testing.assert_array_equal(np.asarray(routes[active]), np.asarray(routes2[active]))
    np.testing.assert_array_equal(np.asarray(logits[active]), np.asarray(logits2[active]))
    assert {k: int(v) for k, v in counters.items()} == {k: int(v) for k, v in counters2.items()}
    assert int(counters["moe_routed"]) == 2 * cfg["num_experts_per_tok"]  # one E layer
    assert int(counters["moe_held"]) == int(counters["moe_routed"])  # all experts held
    assert 1 <= int(counters["experts_touched"]) <= 4
    assert int(counters["expert_load_max"]) <= 2
    nobody = model.apply_decode(params, caches, tokens, pos, jnp.zeros((slots,), bool))[2]
    assert all(int(v) == 0 for v in nobody.values())
    for layer in (0, 3):  # the Mamba layers
        for field in ("conv", "ssm"):
            before, after = getattr(caches[layer], field), getattr(new[layer], field)
            assert float(jnp.abs(after[1] - before[1]).max()) == 0.0
            assert float(jnp.abs(after[0] - before[0]).max()) > 0.0


# --------------------------------------------------------------------- engine


def _requests(vocab, lens):
    rng = np.random.default_rng(0)
    return [Request(rid=i, prompt=rng.integers(0, vocab, n).astype(np.int32),
                    max_new_tokens=m, arrival_time=0.0) for i, (n, m) in enumerate(lens)]


LENS = [(13, 5), (1, 6), (8, 4), (21, 7), (2, 3), (17, 5), (9, 2)]


def test_engine_serves_the_reference_greedy_tokens_through_reused_slots():
    cfg, w, model, params = setup()
    engine = ServingEngine(model, params, ServeConfig(slots=3, max_len=64, prefill_chunk=8))
    reqs = _requests(cfg["vocab_size"], LENS)
    report = engine.run(reqs)
    assert sum(e[0] == "admit" for e in report.events) == len(reqs) > engine.cfg.slots
    for r in reqs:
        served = report.requests[r.rid].tokens
        assert len(served) == r.max_new_tokens
        seq = np.concatenate([r.prompt, np.asarray(served[:-1], np.int32)])
        logits = np.asarray(ref.forward(cfg, w, jnp.asarray(seq)))[len(r.prompt) - 1:]
        gaps = logits.max(-1) - logits[np.arange(len(served)), served]
        assert gaps.max() <= 1e-5, (r.rid, gaps.max())
        # the routes the engine kept: one row a position, prefilled or decoded, and along
        # them the reference goes where it goes alone
        routes = np.concatenate(report.requests[r.rid].routes)
        assert routes.shape == (len(seq), model.route_width) and routes.dtype == np.int32
        followed, regret = ref.served_rows_logits(cfg, w, jnp.asarray(seq), len(r.prompt) - 1,
                                                  len(served), jnp.asarray(routes))
        np.testing.assert_allclose(np.asarray(followed), logits, rtol=1e-5, atol=1e-6)
        assert float(regret.max()) <= 1e-6


def test_engine_run_is_deterministic_and_its_counters_add_up():
    cfg, _, model, params = setup(n_routed_experts=4,
                                  deployment={"n_routed_experts": 8, "held_first": 4})
    model = model_of(cfg, held=(4, 4))
    runs = []
    for _ in range(2):
        engine = ServingEngine(model, params, ServeConfig(
            slots=3, max_len=64, prefill_chunk=8, step_time_s=0.01))
        tracer = Tracer()
        with use_tracer(tracer):
            report = engine.run(_requests(cfg["vocab_size"], LENS))
        runs.append((report.events, {k: v.tokens for k, v in report.requests.items()},
                     [(e.name, e.args) for e in tracer.events if e.cat == "serve"]))
    assert runs[0] == runs[1]
    spans = runs[0][2]
    dispatch = {a["step"]: a for n, a in spans if n == "dispatch"}
    commits = [a for n, a in spans if n == "commit"]
    assert commits and len(commits) == len(dispatch)
    k, layers, held = cfg["num_experts_per_tok"], 1, 4
    for c in commits:
        active = dispatch[c["step"]]["active"]
        assert dispatch[c["step"]]["state_slots"] == active
        assert c["moe_routed"] == active * k * layers
        assert 0 <= c["moe_held"] <= c["moe_routed"]
        assert c["experts_touched"] <= min(held * layers, c["moe_held"])
        assert c["expert_load_max"] <= active
        assert (c["moe_held"] == 0) == (c["experts_touched"] == 0)
    assert 0 < sum(c["moe_held"] for c in commits) < sum(c["moe_routed"] for c in commits)
    admits = [a for n, a in spans if n == "admit"]
    assert len(admits) == len(LENS) and all(a["state_reset"] == 1 for a in admits)


def test_transformer_spans_carry_the_new_counters_at_zero():
    from tpudml.models import TransformerLM

    model = TransformerLM(vocab_size=64, embed_dim=32, num_heads=4, num_layers=1, max_len=32)
    params, _ = model.init(jax.random.key(0))
    tracer = Tracer()
    with use_tracer(tracer):
        ServingEngine(model, params, ServeConfig(slots=2, max_len=32, prefill_chunk=8)).run(
            _requests(64, [(5, 3), (9, 2)]))
    by_name = {e.name: e.args for e in tracer.events if e.cat == "serve"}
    assert by_name["admit"]["state_reset"] == 0 and by_name["dispatch"]["state_slots"] == 0
    assert "moe_routed" not in by_name["commit"]


# --------------------------------------------------------------- capabilities


def _engine(cfg_kw=None, **engine_kw):
    _, _, model, params = setup()
    base = dict(slots=2, max_len=32, prefill_chunk=8)
    return ServingEngine(model, params, ServeConfig(**{**base, **(cfg_kw or {})}), **engine_kw)


def _mesh():
    from tpudml.core.config import MeshConfig
    from tpudml.core.dist import make_mesh

    return make_mesh(MeshConfig({"model": 2}), jax.devices()[:2])


def _handoff(tmp_path):
    from tpudml.serve.fleet.disagg import write_handoff

    _, _, model, params = setup()
    cfg = ServeConfig(slots=2, max_len=32, prefill_chunk=8, cache_layout="paged",
                      page_size=8, prefix_sharing=True)
    return write_handoff(model, params, cfg, np.arange(9, dtype=np.int32), tmp_path)


def _slo(_):
    from tpudml.serve.sched import SLOConfig

    return _engine({"slo": SLOConfig(tpot_budget_s=1.0)})


@pytest.mark.parametrize("key,build", [
    ("serve_pattern_paged", lambda _: _engine({"cache_layout": "paged", "page_size": 8})),
    ("serve_pattern_spec", lambda _: _engine({"spec_k": 2})),
    ("serve_pattern_tp", lambda _: _engine(mesh=_mesh())),
    ("serve_pattern_fused_head", lambda _: _engine({"fused_head": True})),
    ("serve_pattern_weight_quant", lambda _: _engine({"weight_quant": "int8"})),
    ("serve_pattern_slo", _slo),
    ("serve_pattern_handoff", _handoff),
])
def test_levers_not_built_for_a_pattern_model_reject(key, build, tmp_path):
    with pytest.raises(CompositionError) as exc:
        build(tmp_path)
    assert str(exc.value) == TABLE[key].message
    candidate = {"serve_pattern": True, "serve_cache_layout": "paged", "serve_spec_k": 2,
                 "serve_tp": True, "serve_fused_head": True, "serve_weight_quant": "int8",
                 "serve_slo": True, "serve_handoff": True}
    assert TABLE[key].when(candidate) and not TABLE[key].when({**candidate, "serve_pattern": False})
