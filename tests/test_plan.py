"""tpudml.plan: the static autosharding planner's contracts.

Three pinned properties:

- **determinism** — same spec + world → byte-identical ``plan.json``
  (no timestamps, sorted keys, stable candidate ordering);
- **prune honesty** — every enumerated candidate is either a survivor
  or a dropped record carrying its rule and reason: no silent caps;
- **planner ↔ runtime agreement** — the capability table the prune
  pass reads is the same table every engine guard raises from, checked
  in both directions (every table key is raised by some ``reject()``
  call; every ``reject()`` key exists in the table) plus live
  constructor spot-checks that the raised message IS the table message.

The planner's rank order against measured step times is a claim for the
chip (ROADMAP.md A7): a CPU wall-clock comparison is a count, never a
verdict, and no test here makes one.
"""

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def plan4():
    from tpudml.plan import flagship_lm, make_plan

    return make_plan(flagship_lm(), 4)


# ------------------------------------------------------------ determinism


def test_plan_json_is_byte_deterministic(plan4):
    from tpudml.plan import flagship_lm, make_plan, plan_to_json

    again = make_plan(flagship_lm(), 4)
    assert plan_to_json(plan4) == plan_to_json(again)


def test_plan_roundtrips_through_json(plan4, tmp_path):
    from tpudml.plan import load_plan, plan_to_json

    path = tmp_path / "plan.json"
    path.write_text(plan_to_json(plan4))
    assert load_plan(str(path)) == json.loads(plan_to_json(plan4))
    bad = dict(plan4, version=99)
    path.write_text(json.dumps(bad))
    with pytest.raises(ValueError, match="version"):
        load_plan(str(path))


# ---------------------------------------------------------- prune honesty


@pytest.mark.parametrize("world", [4, 8])
def test_prune_reports_every_dropped_candidate(world):
    """No silent caps: survivors + dropped == enumerated, and every drop
    carries a rule and a human-readable reason."""
    from tpudml.plan import enumerate_candidates, flagship_lm, prune

    spec = flagship_lm()
    cands = enumerate_candidates(world)
    survivors, dropped = prune(spec, cands)
    assert len(survivors) + len(dropped) == len(cands)
    assert dropped, "the space deliberately includes rejected combos"
    for rec in dropped:
        assert rec.rule
        assert rec.reason
    # The capability rejections carry the table's exact message.
    from tpudml.capabilities import TABLE

    cap = [r for r in dropped if r.rule.startswith("capability:")]
    assert cap
    for rec in cap:
        key = rec.rule.split(":", 1)[1]
        assert rec.reason == TABLE[key].message


def test_prune_drops_overlap_without_zero1():
    """The enumeration includes table-rejected combos so the report
    demonstrates the shared rules firing (not silently never generating
    them)."""
    from tpudml.plan import enumerate_candidates, flagship_lm, prune

    _, dropped = prune(flagship_lm(), enumerate_candidates(4))
    rules = {r.rule for r in dropped}
    assert "capability:zero1_overlap_needs_zero1" in rules
    assert "capability:pp_fused_xent" in rules


def test_prune_hbm_budget_drops_and_reports():
    from tpudml.plan import enumerate_candidates, flagship_lm, prune

    spec = flagship_lm()
    cands = enumerate_candidates(4)
    # A 1 MB budget is below every candidate's params+moments footprint.
    survivors, dropped = prune(spec, cands, hbm_budget_bytes=1_000_000)
    assert not survivors
    assert {r.rule for r in dropped} >= {"hbm"}


def test_divisibility_prunes_odd_heads():
    from tpudml.plan import ModelSpec, enumerate_candidates, prune

    spec = ModelSpec(vocab_size=256, embed_dim=64, num_heads=3,
                     num_layers=2, seq_len=128, per_chip_batch=4)
    _, dropped = prune(spec, enumerate_candidates(4, engines=["tp"]))
    assert any(r.rule == "divisibility" and "num_heads" in r.reason
               for r in dropped)


# ------------------------------------- capability table <-> runtime guards

_REJECT_RE = re.compile(r"""reject\(\s*["']([a-z0-9_]+)["']""")

_SOURCE_ROOTS = ("tpudml", "tasks")


def _reject_keys_in_source():
    keys = {}
    for root in _SOURCE_ROOTS:
        for dirpath, _, files in os.walk(os.path.join(REPO, root)):
            for fname in files:
                if not fname.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fname)
                with open(path) as fh:
                    for key in _REJECT_RE.findall(fh.read()):
                        keys.setdefault(key, []).append(
                            os.path.relpath(path, REPO))
    return keys


def test_every_runtime_reject_key_is_in_the_table():
    from tpudml.capabilities import TABLE

    used = _reject_keys_in_source()
    assert used, "reject() call sites expected in the engines"
    unknown = {k: v for k, v in used.items() if k not in TABLE}
    assert not unknown, f"reject() keys missing from the table: {unknown}"


def test_every_table_key_is_raised_by_some_runtime_guard():
    from tpudml.capabilities import TABLE

    used = _reject_keys_in_source()
    orphans = [k for k in TABLE if k not in used]
    assert not orphans, (
        f"capability table entries no engine raises: {orphans} — either "
        f"wire the guard through reject() or drop the entry")


def test_runtime_guard_raises_the_table_message():
    """Live spot-checks: constructors raise CompositionError carrying the
    table's exact message for a sample of composition rejections."""
    import jax

    from tpudml.capabilities import CompositionError, TABLE
    from tpudml.core.config import MeshConfig
    from tpudml.core.dist import make_mesh
    from tpudml.models import LeNet
    from tpudml.optim import make_optimizer
    from tpudml.parallel.dp import DataParallel

    mesh = make_mesh(MeshConfig({"data": 2}), jax.devices()[:2])
    model, opt = LeNet(), make_optimizer("sgd", 0.1)
    cases = {
        "zero1_overlap_needs_zero1": dict(zero1_overlap=True),
        "zero1_overlap_needs_accum": dict(zero1=True, zero1_overlap=True,
                                          accum_steps=1),
        "zero1_replaces_aggregation": dict(zero1=True,
                                           aggregation="allgather"),
        "save_scores_needs_fused_xent": dict(save_scores=True),
    }
    for key, kwargs in cases.items():
        with pytest.raises(CompositionError) as exc:
            DataParallel(model, opt, mesh, **kwargs)
        assert str(exc.value) == TABLE[key].message, key


def test_planner_prunes_exactly_what_the_constructor_rejects():
    """Planner/runtime agreement the other way: a candidate the table
    rejects must also fail to construct, with the same message."""
    from tpudml.capabilities import (
        TABLE,
        CompositionError,
        candidate_rejection,
    )
    from tpudml.plan import build_candidate
    from tpudml.plan.space import Candidate, flagship_lm

    cand = Candidate(engine="zero1", mesh=(("data", 2),), zero1=True,
                     zero1_overlap=True, accum_steps=1, fused_xent=False,
                     sentinel=False, obs=False)
    key = candidate_rejection(cand.to_dict())
    assert key == "zero1_overlap_needs_accum"
    with pytest.raises(CompositionError) as exc:
        build_candidate(flagship_lm(), cand)
    assert str(exc.value) == TABLE[key].message


# ----------------------------------------------------- winner verification


def test_winner_verifies_with_zero_dataflow_findings(plan4):
    """Acceptance: every emitted plan passes J112-J116 with zero
    findings, and nothing was demoted to get there."""
    ver = plan4["verification"]
    assert ver["ok"]
    assert ver["demoted"] == []
    dataflow = [f for f in ver["findings"]
                if f["rule"] in ("J112", "J113", "J114", "J115", "J116")]
    assert dataflow == []


def test_fresh_plan_is_j118_clean_and_stale_plan_fires(plan4):
    """predicted is stamped from the verification trace, so a fresh plan
    re-traces clean; doubling the predicted comm must fire J118."""
    from tpudml.plan import plan_drift_findings

    assert [f for f in plan_drift_findings(plan4) if f.rule == "J118"] == []
    stale = json.loads(json.dumps(plan4))
    stale["predicted"]["comm_wire_bytes"] *= 2.0
    fired = [f for f in plan_drift_findings(stale) if f.rule == "J118"]
    assert fired
    assert "re-plan" in fired[0].message


# ------------------------------------------------------------ CLI contract


def test_plan_cli_check_smoke():
    """The tier-1 CI smoke: ``python -m tpudml.plan --check`` plans the
    flagship spec at world 4 and 8 and exits 0 with a verified winner at
    both."""
    proc = subprocess.run(
        [sys.executable, "-m", "tpudml.plan", "--check"],
        capture_output=True, text=True, cwd=REPO, timeout=560,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "world=4: ok" in proc.stdout
    assert "world=8: ok" in proc.stdout


def test_plan_cli_github_format(tmp_path):
    """--format github emits workflow-annotation lines in the same
    grammar as the analysis CLI (``::level ::message``)."""
    out = tmp_path / "plan.json"
    proc = subprocess.run(
        [sys.executable, "-m", "tpudml.plan", "--world", "4",
         "--engines", "dp,zero1", "--format", "github",
         "--out", str(out)],
        capture_output=True, text=True, cwd=REPO, timeout=560,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("::")]
    assert lines and lines[0].startswith("::notice ::PLAN[world=4]: winner ")
    # And the emitted file is a loadable v1 plan.
    from tpudml.plan import PLAN_VERSION, load_plan

    assert load_plan(str(out))["version"] == PLAN_VERSION


def test_analysis_cost_writes_report_fresh(tmp_path, monkeypatch):
    """Satellite pin: ``--cost`` writes analysis/cost_report.json anew
    in the working directory (the file is gitignored, never committed)."""
    monkeypatch.chdir(tmp_path)
    from tpudml.analysis.__main__ import main

    rc = main(["--cost", "--entrypoints", "task2_dp"])
    assert rc == 0
    report = json.loads((tmp_path / "analysis" / "cost_report.json")
                        .read_text())
    assert [e["entrypoint"] for e in report["entrypoints"]] == ["task2_dp"]
    assert report["total_wire_bytes"] > 0


def test_gitignore_covers_generated_reports():
    gitignore = open(os.path.join(REPO, ".gitignore")).read().split("\n")
    assert "analysis/cost_report.json" in gitignore
    assert "analysis/plan.json" in gitignore


# --------------------------------------------------------- train wiring


def test_train_config_merges_plan_engine_config(plan4, tmp_path):
    """--plan plan.json fills TrainConfig knobs left at their defaults;
    explicit CLI flags win."""
    from tpudml.core.config import build_parser, config_from_args
    from tpudml.plan import plan_to_json

    path = tmp_path / "plan.json"
    path.write_text(plan_to_json(plan4))
    ec = plan4["engine_config"]
    assert ec["zero1"] and ec["accum_steps"] == 2  # the dryrun winner

    cfg = config_from_args(build_parser().parse_args(["--plan", str(path)]))
    assert cfg.zero1 == ec["zero1"]
    assert cfg.accum_steps == ec["accum_steps"]

    cfg = config_from_args(build_parser().parse_args(
        ["--plan", str(path), "--accum_steps", "8"]))
    assert cfg.accum_steps == 8  # explicit flag beats the plan


# --------------------------------------------------- v2 schema + calibration


def test_plan_v2_schema_keys_always_present(plan4):
    """v2 totality: calibration/replan are ALWAYS keys (null when unused)
    — schema shape never depends on how the plan was produced, which is
    what keeps byte-determinism trivial."""
    from tpudml.plan import PLAN_VERSION

    assert plan4["version"] == PLAN_VERSION == 2
    assert plan4["calibration"] is None
    assert plan4["replan"] is None


def test_v1_plan_still_loads(plan4, tmp_path):
    """Back-compat: a v1 plan.json (no calibration/replan keys) loads
    and is upgraded in-memory to the v2 shape."""
    from tpudml.plan import load_plan

    v1 = {k: v for k, v in plan4.items() if k not in ("calibration", "replan")}
    v1["version"] = 1
    path = tmp_path / "v1_plan.json"
    path.write_text(json.dumps(v1, indent=2, sort_keys=True) + "\n")
    plan = load_plan(str(path))
    assert plan["version"] == 1
    assert plan["calibration"] is None and plan["replan"] is None
    assert plan["winner"] == plan4["winner"]


def test_calibrated_plan_is_byte_deterministic(tmp_path):
    from tpudml.plan import Calibration, flagship_lm, load_plan, make_plan, plan_to_json

    cal = Calibration(comm_scale=1.25, source="obs/drift")
    replan = {"trigger": "drift", "why": "test", "old_world": 4,
              "old_winner": {}, "receipts": []}
    a = make_plan(flagship_lm(), 4, verify=False, calibration=cal,
                  replan=dict(replan))
    b = make_plan(flagship_lm(), 4, verify=False, calibration=cal,
                  replan=dict(replan))
    assert plan_to_json(a) == plan_to_json(b)
    assert a["calibration"]["comm_scale"] == 1.25
    path = tmp_path / "plan.json"
    path.write_text(plan_to_json(a))
    assert load_plan(str(path)) == json.loads(plan_to_json(a))


def test_calibration_scales_the_roofline_terms():
    """comm_scale multiplies every comm term, hbm_scale the HBM estimate
    — monotonically, so a measured-slower network can only demote
    comm-heavy candidates, never spuriously promote them."""
    from tpudml.plan import flagship_lm, score_candidate
    from tpudml.plan.score import Calibration
    from tpudml.plan.space import enumerate_candidates

    spec = flagship_lm()
    cand = next(c for c in enumerate_candidates(4, engines=["zero1"])
                if c.zero1 and not c.zero1_overlap)
    base = score_candidate(spec, cand)
    cal = score_candidate(spec, cand,
                          calibration=Calibration(comm_scale=2.0))
    assert cal.comm_wire_bytes == pytest.approx(2.0 * base.comm_wire_bytes)
    assert (cal.exposed_comm_s + cal.hidden_comm_s) == pytest.approx(
        2.0 * (base.exposed_comm_s + base.hidden_comm_s))
    assert cal.step_time_s > base.step_time_s
    assert cal.compute_s == base.compute_s  # comm scale touches only comm
    hbm = score_candidate(spec, cand,
                          calibration=Calibration(hbm_scale=1.5))
    assert hbm.est_hbm_bytes == pytest.approx(1.5 * base.est_hbm_bytes, rel=1e-6)


def test_calibration_fit_and_roundtrip():
    from tpudml.plan import Calibration

    records = [
        {"entrypoint": "a", "static_wire_bytes": 1.0e6,
         "measured_wire_bytes": 1.25e6, "rel_err": 0.2},
        {"entrypoint": "b", "static_wire_bytes": 4.0e5,
         "measured_wire_bytes": 5.0e5, "rel_err": 0.2},
    ]
    cal = Calibration.from_drift_records(records)
    assert cal.comm_scale == pytest.approx(1.75e6 / 1.4e6)
    assert len(cal.basis) == 2
    assert Calibration.from_dict(cal.to_dict()).comm_scale == cal.comm_scale


def test_world1_enumeration_is_dp_only():
    """World 1: only plain DP is enumerable — sharding chains (zero1 /
    fsdp / tp) have nothing to shard, so the planner reports them as
    infeasible rather than scoring degenerate single-chip variants."""
    from tpudml.plan import flagship_lm, make_plan
    from tpudml.plan.space import enumerate_candidates

    cands = enumerate_candidates(1)
    assert cands
    assert {c.engine for c in cands} == {"dp"}

    plan = make_plan(flagship_lm(), 1, engines=["dp", "zero1"], verify=False)
    assert plan["winner"]["candidate"]["engine"] == "dp"
    assert plan["winner"]["candidate"]["mesh"] == {"data": 1}
