"""tpudml.analysis: every rule fires on its seeded fixture and stays
silent on the clean twin, the jaxpr pass traces the real engine
entrypoints, and ``--strict`` with the committed allowlist is green.

The jaxpr fixtures are built inline (tiny jitted functions with one
deliberate hazard each); the AST fixtures live in analysis_fixtures/.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudml.analysis import (
    analyze_callable,
    analyze_entrypoint,
    analyze_file,
    donation_findings,
    load_allowlist,
    split_allowed,
)

FIXTURES = os.path.join(os.path.dirname(__file__), "analysis_fixtures")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rules(findings):
    return {f.rule for f in findings}


# ------------------------------------------------------------- AST pass


def test_ast_rules_fire_on_seeded_fixtures():
    findings = analyze_file(os.path.join(FIXTURES, "seeded_violations.py"))
    assert {"A201", "A202", "A203", "A204"} <= _rules(findings)
    # A201 fires on both the if and the for
    assert sum(1 for f in findings if f.rule == "A201") == 2
    # every finding points at a real line with a hint
    for f in findings:
        assert f.line > 0 and f.hint


def test_ast_rules_silent_on_clean_fixtures():
    assert analyze_file(os.path.join(FIXTURES, "clean.py")) == []


# ------------------------------------------------------------ jaxpr pass


def test_j101_unbound_axis_fires_and_bound_is_silent():
    bad = analyze_callable(
        lambda x: jax.lax.psum(x, "ghost"), (jnp.ones((4,)),), "fix-j101")
    assert _rules(bad) == {"J101"}

    from tpudml.core.config import MeshConfig
    from tpudml.core.dist import make_mesh
    from tpudml.parallel.sharding import shard_map_fn
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh(MeshConfig({"data": 2}), jax.devices()[:2])
    good_fn = jax.jit(shard_map_fn(
        lambda x: jax.lax.psum(x, "data"), mesh,
        in_specs=(P("data"),), out_specs=P()))
    good = analyze_callable(good_fn, (jnp.ones((4,)),), "ok-j101")
    assert "J101" not in _rules(good)


def test_j102_divergent_branch_collectives():
    from tpudml.core.config import MeshConfig
    from tpudml.core.dist import make_mesh
    from tpudml.parallel.sharding import shard_map_fn
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh(MeshConfig({"data": 2}), jax.devices()[:2])

    def diverging(x):
        return jax.lax.cond(
            x[0] > 0,
            lambda v: jax.lax.psum(v, "data"),  # collective in ONE arm only
            lambda v: v * 2.0,
            x,
        )

    def balanced(x):
        return jax.lax.cond(
            x[0] > 0,
            lambda v: jax.lax.psum(v, "data"),
            lambda v: jax.lax.psum(v * 2.0, "data"),
            x,
        )

    def wrap(fn):
        return jax.jit(shard_map_fn(
            fn, mesh, in_specs=(P("data"),), out_specs=P(None)))

    bad = analyze_callable(wrap(diverging), (jnp.ones((4,)),), "fix-j102")
    assert "J102" in _rules(bad)
    good = analyze_callable(wrap(balanced), (jnp.ones((4,)),), "ok-j102")
    assert "J102" not in _rules(good)


def test_j103_host_callback():
    def chatty(x):
        jax.debug.print("loss={l}", l=x.sum())
        return x * 2.0

    bad = analyze_callable(jax.jit(chatty), (jnp.ones((4,)),), "fix-j103")
    assert "J103" in _rules(bad)
    good = analyze_callable(
        jax.jit(lambda x: x * 2.0), (jnp.ones((4,)),), "ok-j103")
    assert "J103" not in _rules(good)


def test_j104_upcast_outside_accumulation():
    x16 = jnp.ones((8,), jnp.bfloat16)
    bad = analyze_callable(
        lambda x: x.astype(jnp.float32) * 2.0, (x16,), "fix-j104")
    assert "J104" in _rules(bad)
    # upcast feeding a reduction is the intended accumulate-in-f32 idiom
    good = analyze_callable(
        lambda x: jnp.sum(x.astype(jnp.float32)), (x16,), "ok-j104")
    assert "J104" not in _rules(good)


def test_j105_large_closure_constant():
    big = np.ones((600, 600), np.float32)  # 1.44 MiB
    bad = analyze_callable(
        lambda x: x + jnp.asarray(big)[0, 0], (jnp.ones((2,)),), "fix-j105")
    assert "J105" in _rules(bad)
    small = np.ones((8, 8), np.float32)
    good = analyze_callable(
        lambda x: x + jnp.asarray(small)[0, 0], (jnp.ones((2,)),), "ok-j105")
    assert "J105" not in _rules(good)


def test_j106_undonated_buffers():
    state = jnp.ones((1024, 512), jnp.float32)  # 2 MiB
    x = jnp.ones((4,), jnp.float32)

    def step(s, v):
        return s + v.sum(), v * 2.0

    bad = analyze_callable(
        jax.jit(step), (state, x), "fix-j106", expects_donation=True)
    assert "J106" in _rules(bad)
    good = analyze_callable(
        jax.jit(step, donate_argnums=(0,)), (state, x), "ok-j106",
        expects_donation=True)
    assert "J106" not in _rules(good)


def test_j107_vocab_sharded_unsharded_head():
    """J107 fires when the UNSHARDED fused head consumes a vocab-sharded
    kernel inside shard_map — including through the 2-D W all_gather —
    and stays silent for the shard-merge wrapper and a replicated W."""
    from jax.sharding import PartitionSpec as P

    from tpudml.core.config import MeshConfig
    from tpudml.core.dist import make_mesh
    from tpudml.ops.xent_kernel import (
        linear_cross_entropy,
        sharded_linear_cross_entropy,
    )
    from tpudml.parallel.sharding import shard_map_fn

    mesh = make_mesh(MeshConfig({"data": 2}), jax.devices()[:2])
    x = jnp.zeros((8, 4))
    w = jnp.zeros((4, 32))
    lab = jnp.zeros((8,), jnp.int32)

    def wrap(body, w_spec):
        return shard_map_fn(
            body, mesh, in_specs=(P(), w_spec, P()), out_specs=P())

    hazard = wrap(
        lambda x, w, ln: linear_cross_entropy(x, w, ln), P(None, "data"))
    assert "J107" in _rules(analyze_callable(hazard, (x, w, lab), "fix-j107"))

    fixed = wrap(
        lambda x, w, ln: sharded_linear_cross_entropy(
            x, w, ln, axis_name="data"),
        P(None, "data"))
    assert "J107" not in _rules(analyze_callable(fixed, (x, w, lab), "ok"))

    replicated = wrap(
        lambda x, w, ln: linear_cross_entropy(x, w, ln), P())
    assert "J107" not in _rules(
        analyze_callable(replicated, (x, w, lab), "ok-replicated"))

    # 2-D form: W sharded P(data, model); the dim-0 gather over "data"
    # must not launder the vocab-dim sharding over "model".
    mesh2 = make_mesh(MeshConfig({"data": 2, "model": 2}), jax.devices()[:4])

    def hazard2d(x, w, ln):
        def body(x, w, ln):
            k = jax.lax.all_gather(w, "data", axis=0, tiled=True)
            return linear_cross_entropy(x, k, ln)
        return shard_map_fn(
            body, mesh2, in_specs=(P(), P("data", "model"), P()),
            out_specs=P())(x, w, ln)

    bad2d = analyze_callable(hazard2d, (x, w, lab), "fix-j107-2d")
    assert "J107" in _rules(bad2d)
    (f,) = [f for f in bad2d if f.rule == "J107"]
    assert "model" in f.message and "sharded_linear_cross_entropy" in f.message


def test_j107_marker_names_match_kernel_module():
    """The pass keys on string literals so it never imports kernel code;
    this is the drift pin."""
    from tpudml.analysis import jaxpr_pass
    from tpudml.ops import xent_kernel

    assert jaxpr_pass.FUSED_XENT_NAME == xent_kernel.FUSED_XENT_MARKER
    assert jaxpr_pass.SHARDED_XENT_NAME == xent_kernel.SHARDED_XENT_MARKER


def test_j108_replicated_update_under_data_axis():
    """J108 fires on the replicated-DP shape (≥2 gradient psums over a
    data axis, matching outputs returned replicated, no reduce-scatter)
    and stays silent for the ZeRO-1 shape (psum_scatter present) and for
    the FSDP shape (outputs sharded over the axis)."""
    from jax.sharding import PartitionSpec as P

    from tpudml.core.config import MeshConfig
    from tpudml.core.dist import make_mesh
    from tpudml.parallel.sharding import shard_map_fn

    mesh = make_mesh(MeshConfig({"data": 2}), jax.devices()[:2])
    p1, p2 = jnp.ones((8, 4)), jnp.ones((16,))
    x = jnp.ones((4, 4))

    def replicated_update(p1, p2, x):
        s = x.sum()
        g1 = jax.lax.pmean(p1 * s, "data")
        g2 = jax.lax.pmean(p2 * s, "data")
        return p1 - 0.1 * g1, p2 - 0.1 * g2

    bad = shard_map_fn(
        replicated_update, mesh,
        in_specs=(P(), P(), P("data")), out_specs=(P(), P()))
    found = analyze_callable(bad, (p1, p2, x), "fix-j108")
    assert "J108" in _rules(found)
    (f,) = [f for f in found if f.rule == "J108"]
    assert "reduce-scatter" in f.message

    def zero1_update(p1, p2, x):
        s = x.sum()
        c1 = jax.lax.psum_scatter(
            (p1 * s).reshape(-1), "data", scatter_dimension=0, tiled=True)
        c2 = jax.lax.psum_scatter(
            p2 * s, "data", scatter_dimension=0, tiled=True)
        n1 = jax.lax.all_gather(c1 / 2, "data", axis=0, tiled=True)
        n2 = jax.lax.all_gather(c2 / 2, "data", axis=0, tiled=True)
        return p1 - 0.1 * n1.reshape(p1.shape), p2 - 0.1 * n2

    ok_z = shard_map_fn(
        zero1_update, mesh,
        in_specs=(P(), P(), P("data")), out_specs=(P(), P()))
    assert "J108" not in _rules(analyze_callable(ok_z, (p1, p2, x), "ok-z1"))

    def sharded_out_update(p1, p2, x):
        s = x.sum()
        g1 = jax.lax.pmean(p1 * s, "data")
        g2 = jax.lax.pmean(p2 * s, "data")
        return p1 - 0.1 * g1, p2 - 0.1 * g2

    ok_f = shard_map_fn(
        sharded_out_update, mesh,
        in_specs=(P(), P(), P("data")), out_specs=(P("data"), P("data")))
    assert "J108" not in _rules(
        analyze_callable(ok_f, (p1, p2, x), "ok-fsdp"))


@pytest.mark.parametrize("ragged_dw", ["stock", "grouped"])
def test_j109_ragged_transpose_backward(ragged_dw):
    """J109 fires on lax.ragged_dot's stock grouped-transpose dW (both
    dW sites of the two-matmul FFN — the E-scaled masked batched
    dot_general) and stays silent when the grouped-dW custom_vjp
    (ops.moe_kernel.ragged_ffn, the default) owns the backward."""
    from tpudml.core.prng import seed_key
    from tpudml.nn.moe import MoELayer

    moe = MoELayer(16, 4, mlp_ratio=2, dispatch="ragged",
                   ragged_dw=ragged_dw)
    params, _ = moe.init(seed_key(0))
    x = jnp.ones((32, 16))

    def loss(p, x):
        y, st = moe.apply(p, {}, x)
        return jnp.sum(y**2) + st["aux_loss"]

    findings = analyze_callable(
        jax.jit(jax.grad(loss)), (params, x), f"j109-{ragged_dw}")
    fired = [f for f in findings if f.rule == "J109"]
    if ragged_dw == "stock":
        assert len(fired) == 2, findings  # dW1 and dW2
        assert all("4×" in f.message and f.line > 0 for f in fired)
    else:
        assert fired == [], fired


def test_j110_cacheless_decode_fires_and_cached_is_silent():
    """J110 fires on a decode-marked program that recomputes the full
    [T, T] attention per emitted token (the fixture
    analysis_fixtures/cacheless_decode.py) and stays silent on the
    KV-cached step whose softmax is [B, H, 1, L]."""
    from analysis_fixtures.cacheless_decode import make_cacheless_decode_step

    from tpudml.models import TransformerLM
    from tpudml.serve import ServeConfig, ServingEngine

    lm = TransformerLM(vocab_size=32, embed_dim=16, num_heads=2,
                       num_layers=2, max_len=16, rope=True)
    params, _ = lm.init(jax.random.key(0))
    bad = analyze_callable(
        make_cacheless_decode_step(lm), (params, np.zeros((2, 12), np.int32)),
        "j110-cacheless")
    fired = [f for f in bad if f.rule == "J110"]
    assert len(fired) == 1, bad  # one finding per marked program, not per layer
    assert "full-sequence" in fired[0].message and fired[0].hint

    eng = ServingEngine(
        lm, params, ServeConfig(slots=2, max_len=16, prefill_chunk=4))
    good = analyze_callable(
        eng._decode,
        (params, eng.caches, np.zeros(2, np.int32), np.zeros(2, np.int32)),
        "j110-cached")
    assert [f for f in good if f.rule == "J110"] == [], good


def test_j110_marker_name_matches_serve_module():
    """Same drift pin as J107: the analyzer's string literal must equal
    the marker the serving engine jits its decode step under."""
    from tpudml.analysis import jaxpr_pass
    from tpudml.serve import engine

    assert jaxpr_pass.SERVE_DECODE_NAME == engine.SERVE_DECODE_MARKER


def test_j117_marker_names_match_serve_modules():
    """Drift pin for the paged/spec decode markers J117 keys on — and
    they must NOT collide with the dense marker (the spec window softmax
    would false-fire J110's single-token contract)."""
    from tpudml.analysis import jaxpr_pass
    from tpudml.serve import paged, spec

    assert set(jaxpr_pass.PAGED_DECODE_NAMES) == {
        paged.PAGED_DECODE_MARKER, spec.SPEC_DECODE_MARKER}
    assert jaxpr_pass.SERVE_DECODE_NAME not in jaxpr_pass.PAGED_DECODE_NAMES


def test_j117_silent_on_real_paged_and_spec_steps():
    """The shipped paged decode step (table gather) and the paged spec
    step must trace J117-silent — and J110-silent too, their softmax
    widths being none of the rule's business under their own markers."""
    from tpudml.models import TransformerLM
    from tpudml.serve import ServeConfig, ServingEngine

    lm = TransformerLM(vocab_size=32, embed_dim=16, num_heads=2,
                       num_layers=2, max_len=16, rope=True)
    params, _ = lm.init(jax.random.key(0))
    eng = ServingEngine(
        lm, params,
        ServeConfig(slots=2, max_len=16, prefill_chunk=4,
                    cache_layout="paged", page_size=4, num_pages=9,
                    spec_k=2))
    table = np.zeros((2, eng.cfg.max_pages), np.int32)
    toks = np.zeros(2, np.int32)
    pos = np.zeros(2, np.int32)
    plain = analyze_callable(
        eng._decode, (params, eng.caches, table, toks, pos), "j117-paged")
    assert [f for f in plain if f.rule in ("J110", "J117")] == [], plain
    spec = analyze_callable(
        eng._spec,
        (params, eng._dparams, eng.caches, eng._dcaches, table, toks, pos),
        "j117-paged-spec")
    assert [f for f in spec if f.rule in ("J110", "J117")] == [], spec


def test_j119_unfused_tail_fires_and_fused_is_silent():
    """J119 fires once on the stock dense decode step (materialized
    [B, V] logits + separate argmax tail) and stays silent — J110 too —
    when ServeConfig(fused_head=True) routes the tail through the fused
    head marker, whose INTERNAL argmax the scan must skip."""
    from tpudml.models import TransformerLM
    from tpudml.serve import ServeConfig, ServingEngine

    lm = TransformerLM(vocab_size=32, embed_dim=16, num_heads=2,
                       num_layers=2, max_len=16, rope=True)
    params, _ = lm.init(jax.random.key(0))

    def args(eng):
        return (params, eng.caches, np.zeros(2, np.int32),
                np.zeros(2, np.int32))

    plain = ServingEngine(
        lm, params, ServeConfig(slots=2, max_len=16, prefill_chunk=4))
    bad = analyze_callable(plain._decode, args(plain), "j119-unfused")
    fired = [f for f in bad if f.rule == "J119"]
    assert len(fired) == 1, bad  # one finding per marked program
    assert "full-vocab" in fired[0].message and fired[0].line > 0
    assert fired[0].hint

    fused = ServingEngine(
        lm, params,
        ServeConfig(slots=2, max_len=16, prefill_chunk=4, fused_head=True))
    good = analyze_callable(fused._decode, args(fused), "j119-fused")
    assert [f for f in good if f.rule in ("J110", "J119")] == [], good


def test_j119_fires_on_paged_tail_too():
    """The paged decode step's tail is the same unfused argmax — J119
    covers every decode-marked program, not just the dense one."""
    from tpudml.models import TransformerLM
    from tpudml.serve import ServeConfig, ServingEngine

    lm = TransformerLM(vocab_size=32, embed_dim=16, num_heads=2,
                       num_layers=2, max_len=16, rope=True)
    params, _ = lm.init(jax.random.key(0))
    eng = ServingEngine(
        lm, params,
        ServeConfig(slots=2, max_len=16, prefill_chunk=4,
                    cache_layout="paged", page_size=4, num_pages=9))
    table = np.zeros((2, eng.cfg.max_pages), np.int32)
    found = analyze_callable(
        eng._decode,
        (params, eng.caches, table, np.zeros(2, np.int32),
         np.zeros(2, np.int32)),
        "j119-paged")
    assert len([f for f in found if f.rule == "J119"]) == 1, found


def test_j119_overlap_claim_verified_against_marker():
    """The overlap half: a plan whose winner claims ``tp_overlap`` must
    see the TP_OVERLAP_NAME pjit in the traced program — a program
    routed through tp_overlap_matmul passes, a plain matmul program
    fires, and an unclaiming plan checks nothing."""
    from jax.sharding import PartitionSpec as P

    from tpudml.core.config import MeshConfig
    from tpudml.core.dist import make_mesh
    from tpudml.parallel.overlap import tp_overlap_matmul
    from tpudml.parallel.sharding import shard_map_fn

    mesh = make_mesh(MeshConfig({"model": 4}), jax.devices()[:4])
    x = jnp.ones((8, 16), jnp.float32)
    w = jnp.ones((16, 8), jnp.float32)

    def claiming(key):
        return {"winner": {"candidate": {"tp_overlap": True, "key": key}}}

    overlapped = jax.jit(shard_map_fn(
        lambda x, w: tp_overlap_matmul(x, w, axis_name="model"),
        mesh, in_specs=(P(), P(None, "model")), out_specs=P()))
    ok = analyze_callable(
        overlapped, (x, w), "j119-overlap-ok", plan=claiming("t1"))
    assert [f for f in ok if f.rule == "J119"] == [], ok

    plain = jax.jit(shard_map_fn(
        lambda x, w: jax.lax.psum(x @ w, "model"),
        mesh, in_specs=(P(), P(None, "model")), out_specs=P()))
    bad = analyze_callable(
        plain, (x, w), "j119-overlap-bad", plan=claiming("t1"))
    fired = [f for f in bad if f.rule == "J119"]
    assert len(fired) == 1 and "tp_overlap" in fired[0].message, bad

    unclaiming = {"winner": {"candidate": {"tp_overlap": False, "key": "t0"}}}
    silent = analyze_callable(
        plain, (x, w), "j119-no-claim", plan=unclaiming)
    assert [f for f in silent if f.rule == "J119"] == [], silent


def test_j119_marker_names_match_modules():
    """Drift pins for the fused-head and overlap markers J119 keys on —
    same discipline as the J107/J110/J117 pins."""
    from tpudml.analysis import jaxpr_pass
    from tpudml.ops import decode_head
    from tpudml.parallel import overlap

    assert set(jaxpr_pass.FUSED_HEAD_NAMES) == {
        decode_head.FUSED_HEAD_MARKER, decode_head.FUSED_HEAD_INT8_MARKER}
    assert jaxpr_pass.TP_OVERLAP_NAME == overlap.TP_OVERLAP_MARKER


def test_j100_trace_failure_becomes_finding():
    def broken(x):
        return x + jnp.ones((x.shape[0] + 1,))  # shape mismatch at trace

    bad = analyze_callable(broken, (jnp.ones((4,)),), "fix-j100")
    assert _rules(bad) == {"J100"}
    good = analyze_callable(lambda x: x + 1.0, (jnp.ones((4,)),), "ok-j100")
    assert "J100" not in _rules(good)


def test_donation_parser_reads_aliasing():
    state = jnp.ones((1024, 512), jnp.float32)
    lowered = jax.jit(
        lambda s: s * 2.0, donate_argnums=(0,)).lower(state).as_text()
    assert donation_findings(lowered, "donated") == []
    lowered_not = jax.jit(lambda s: s * 2.0).lower(state).as_text()
    assert [f.rule for f in donation_findings(lowered_not, "plain")] == ["J106"]


# ----------------------------------------------- real engine entrypoints


@pytest.mark.parametrize(
    "name",
    ["task2_dp", "dp_zero1", "dp_sentinel", "fsdp", "pp_gpipe", "tp_fused",
     "fsdp_fused", "moe_ragged", "serve_decode", "serve_paged_decode"])
def test_entrypoints_trace_on_cpu(name):
    """The acceptance floor: the DP, FSDP, and pipeline steps trace and
    analyze without TPU hardware, with no error-severity findings and
    nothing outside the committed allowlist."""
    findings = analyze_entrypoint(name)
    assert not [f for f in findings if f.severity == "error"], findings
    entries = load_allowlist(os.path.join(REPO, "analysis", "allowlist.toml"))
    active, _ = split_allowed(findings, entries)
    assert active == [], active


# ------------------------------------------------------------ CLI smoke


def test_strict_cli_green_on_repo():
    """CI contract: the committed allowlist covers the whole repo."""
    proc = subprocess.run(
        [sys.executable, "-m", "tpudml.analysis", "--strict"],
        cwd=REPO, capture_output=True, text=True, timeout=540,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 finding(s)" in proc.stdout
    # Full-surface strict run: no committed suppression may be stale.
    assert "stale allowlist" not in proc.stdout


def test_j111_unguarded_update_fires_and_sentinel_is_silent():
    """J111 fires on a plain training step (parameter-update subs with no
    finiteness predicate anywhere in the program), anchors at the
    optimizer file so ONE allowlist entry covers every plain engine, and
    goes silent the moment the step carries a GradSentinel — whose
    isfinite lowers to the is_finite primitive the rule looks for."""
    plain = analyze_entrypoint("task2_dp")
    fired = [f for f in plain if f.rule == "J111"]
    assert len(fired) == 1, plain
    assert fired[0].severity == "info"
    assert fired[0].file == "tpudml/optim/optimizers.py"
    assert "is_finite" in fired[0].message

    guarded = analyze_entrypoint("dp_sentinel")
    assert [f for f in guarded if f.rule == "J111"] == [], guarded
    # And the sentinel engine introduces nothing else un-allowlisted.
    entries = load_allowlist(os.path.join(REPO, "analysis", "allowlist.toml"))
    active, _ = split_allowed(guarded, entries)
    assert active == [], active


def test_j111_allowlist_covers_plain_engines():
    """The committed allowlist's single optimizers.py entry absorbs the
    by-design finding on the plain baseline entrypoints."""
    findings = analyze_entrypoint("task2_dp")
    entries = load_allowlist(os.path.join(REPO, "analysis", "allowlist.toml"))
    active, allowed = split_allowed(findings, entries)
    assert [f for f in active if f.rule == "J111"] == []
    assert any(f.rule == "J111" for f in allowed)


# ----------------------------------- dataflow rules (J112-J116) fixtures


JAXPR_FIXDIR = os.path.join(FIXTURES, "jaxpr")


def _jaxpr_fixture_names():
    return sorted(f[:-3] for f in os.listdir(JAXPR_FIXDIR)
                  if f.endswith(".py") and f != "__init__.py")


@pytest.mark.parametrize("name", _jaxpr_fixture_names())
def test_dataflow_fixture(name):
    """One test per module in analysis_fixtures/jaxpr/ — discovery is by
    filename, so a fixture that fails to import or build fails THIS test
    under its own name instead of aborting collection with an opaque
    parametrize error. Protocol: see that directory's __init__.py."""
    import importlib.util

    path = os.path.join(JAXPR_FIXDIR, name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    try:
        spec.loader.exec_module(mod)
    except Exception as e:  # noqa: BLE001 - reported with the fixture name
        pytest.fail(f"fixture {name}: import failed: {e!r}")
    missing = [a for a in ("RULE", "EXPECT", "build") if not hasattr(mod, a)]
    if missing:
        pytest.fail(f"fixture {name}: missing {missing} "
                    "(protocol in analysis_fixtures/jaxpr/__init__.py)")
    try:
        fn, fargs = mod.build()
    except Exception as e:  # noqa: BLE001 - reported with the fixture name
        pytest.fail(f"fixture {name}: build() failed: {e!r}")

    findings = analyze_callable(
        fn, fargs, entrypoint=name, **getattr(mod, "ANALYZE_KWARGS", {}))
    fired = [f for f in findings if f.rule == mod.RULE]
    if mod.EXPECT == "fire":
        assert fired, (name, findings)
        assert all(f.hint for f in fired)
    else:
        assert fired == [], (name, fired)


def test_jaxpr_fixture_dir_covers_every_dataflow_rule():
    """Each dataflow rule ships a firing seeded-bug fixture AND a silent
    correct-code twin; a deleted fixture file fails here by rule name."""
    names = _jaxpr_fixture_names()
    for rule in ("j112", "j113", "j114", "j115", "j116", "j117", "j118"):
        kinds = {n.rsplit("_", 1)[1] for n in names if n.startswith(rule)}
        assert kinds == {"fire", "silent"}, (rule, kinds)


# ------------------------------------------- dataflow lattice fixpoint


@pytest.mark.parametrize("name", ["serve_decode", "dp_sentinel"])
def test_dataflow_converges_on_looping_entrypoints(name):
    """The lattice fixpoint must settle within its iteration cap on the
    entrypoints with the most control flow: the serving decode step
    (scan + caches) and the sentinel ZeRO-1 step (is_finite cond around
    the sharded update)."""
    from tpudml.analysis.dataflow import _MAX_FIXPOINT_ITERS, analyze_dataflow
    from tpudml.analysis.entrypoints import ENTRYPOINTS

    prog = ENTRYPOINTS[name]()[0]
    closed = jax.make_jaxpr(prog.fn)(*prog.args)
    flow = analyze_dataflow(closed, name, in_specs=prog.in_specs,
                            mesh_axes=prog.mesh_axes)
    assert flow.converged, flow
    assert flow.iterations < _MAX_FIXPOINT_ITERS
    assert not [f for f in flow.findings if f.severity == "error"], flow


# --------------------------- static cost vs measured CommStats (5% pin)


@pytest.mark.parametrize("zero1", [False, True], ids=["dp", "zero1"])
def test_static_cost_matches_measured_comm_bytes(zero1):
    """Acceptance pin: the --cost byte counts for the DP and ZeRO-1
    steps agree with the measured-path CommStats accounting within 5%
    on a world-4 mesh. Both sides price the same ring model
    (comm.timing.collective_wire_bytes), so this checks the static
    interpreter's event inventory — collective kinds, payload bytes,
    trip counts — against the program the engine actually times."""
    from tpudml.analysis.dataflow import analyze_dataflow
    from tpudml.core.config import MeshConfig
    from tpudml.core.dist import make_mesh
    from tpudml.core.prng import seed_key
    from tpudml.models import LeNet
    from tpudml.optim import make_optimizer
    from tpudml.parallel.dp import DataParallel

    mesh = make_mesh(MeshConfig({"data": 4}), jax.devices()[:4])
    rng = np.random.default_rng(0)
    x = rng.normal(size=(8, 28, 28, 1)).astype(np.float32)
    y = rng.integers(0, 10, size=(8,)).astype(np.int32)
    opt = "adam" if zero1 else "sgd"

    measured_dp = DataParallel(
        LeNet(), make_optimizer(opt, 0.01), mesh,
        measure_comm=True, zero1=zero1)
    ts = measured_dp.create_state(seed_key(0))
    measured_dp.make_train_step()(ts, x, y)
    measured = measured_dp.comm_stats.comm_bytes
    assert measured > 0.0

    static_dp = DataParallel(
        LeNet(), make_optimizer(opt, 0.01), mesh, zero1=zero1)
    ts2 = static_dp.create_state(seed_key(0))
    fused = static_dp.make_train_step()
    closed = jax.make_jaxpr(fused.jitted)(ts2, x, y)
    flow = analyze_dataflow(closed, f"xval-{opt}", in_specs=fused.in_specs,
                            mesh_axes=fused.mesh_axes)
    static = sum(ev.wire_bytes * ev.trips for ev in flow.comm_events)
    assert abs(static - measured) / measured <= 0.05, (static, measured)


# --------------------------------------------- stale allowlist entries


def test_stale_allowlist_entries_detected():
    """unused_entries flags suppressions whose finding no longer exists
    (and only those), so --strict can warn before an allowlist entry
    silently outlives its bug."""
    from tpudml.analysis.allowlist import AllowEntry, unused_entries
    from tpudml.analysis.findings import Finding

    live = AllowEntry(rule="J111", path="tpudml/optim/*",
                      reason="plain engines omit the sentinel by design")
    live_line = AllowEntry(rule="A201", path="tools/*.py", line=12,
                           reason="host-side CLI glue")
    stale = AllowEntry(rule="J105", path="tpudml/nn/old_layer.py",
                       reason="fixed in the ragged-dW rework")
    wrong_line = AllowEntry(rule="A201", path="tools/*.py", line=99,
                            reason="drifted line anchor")
    findings = [
        Finding("J111", "no finiteness gate",
                file="tpudml/optim/optimizers.py", line=40),
        Finding("A201", "python if on traced value",
                file="tools/report.py", line=12),
    ]
    entries = [live, live_line, stale, wrong_line]
    assert unused_entries(findings, entries) == [stale, wrong_line]
    assert unused_entries(findings, [live, live_line]) == []


# ------------------------------------------------ CLI output formats


def _run_cli(*cli_args, timeout=180):
    return subprocess.run(
        [sys.executable, "-m", "tpudml.analysis", *cli_args],
        cwd=REPO, capture_output=True, text=True, timeout=timeout,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


def test_cli_format_json_golden():
    """--format json emits one machine-readable object with the three
    fixed keys; every finding carries rule/severity/location. Scoped to
    the seeded AST fixture (fast, deterministic — no tracing)."""
    import json

    proc = _run_cli(
        "--skip-jaxpr", "--format", "json", "--paths",
        os.path.join("tests", "analysis_fixtures", "seeded_violations.py"))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout)
    assert set(out) == {"active", "allowed", "stale_allowlist"}
    # Partial runs never judge staleness (they see a partial surface).
    assert out["stale_allowlist"] == []
    assert {f["rule"] for f in out["active"]} >= {"A201", "A202", "A203",
                                                 "A204"}
    for f in out["active"]:
        assert f["file"].endswith("seeded_violations.py")
        assert f["line"] > 0
        assert f["severity"] in ("error", "warn", "info")


def test_cli_format_github_golden():
    """--format github emits only workflow-annotation lines, each with a
    file= (and line=) location and a '::'-free message so the annotation
    cannot be truncated by the runner."""
    import re

    proc = _run_cli(
        "--skip-jaxpr", "--format", "github", "--paths",
        os.path.join("tests", "analysis_fixtures", "seeded_violations.py"))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    lines = [ln for ln in proc.stdout.splitlines() if ln]
    assert lines, proc.stdout
    shape = re.compile(
        r"^::(error|warning|notice) file=[^,]+,line=\d+::[AJ]\d{3}")
    for ln in lines:
        assert shape.match(ln), ln
        _, _, message = ln.split("::", 2)
        assert "::" not in message, ln
    assert any("A201" in ln for ln in lines)
