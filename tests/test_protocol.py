"""Cross-rank protocol pass (P300–P304): model, rules, gate, fixtures.

Covers the PR 19 surface end to end:

- the fixture-twin contract: every P rule fires on its broken twin and
  stays silent on its healthy twin (filename-keyed discovery under
  ``tests/analysis_fixtures/protocol/``, coverage-pinned);
- the re-mesh property: every single-slot ``replace_pipeline`` shrink
  of the drill's [2,2] pipeline and the 3-stage [2,2,2] spec yields a
  P300/P301-clean schedule — re-mesh never emits an undeliverable
  frame;
- the committed meshless fixtures validate against the schedule model
  (every replayed transfer is a modeled frame; tampered streams fire);
- the ``--protocol`` CLI: strict-clean on the repo, byte-deterministic;
- the MPMDController pre-launch gate: a rejected spec never spawns and
  leaves machine-readable receipts; a clean spec records its receipts
  and launches.
"""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
FIXDIR = REPO / "tests" / "analysis_fixtures" / "protocol"

from tpudml.analysis.ast_pass import analyze_file  # noqa: E402
from tpudml.analysis.protocol import (  # noqa: E402
    analyze_pipeline,
    analyze_protocol_surface,
    build_schedules,
    check_schedules,
    protocol_surface,
    validate_fixture_events,
)
from tpudml.mpmd.spec import replace_pipeline  # noqa: E402


def _fixture_names() -> list:
    return sorted(
        p.stem for p in FIXDIR.glob("p*_*.py") if p.name != "__init__.py"
    )


def _load_fixture(name: str):
    path = FIXDIR / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"protofix_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod, path


# ------------------------------------------------------- fixture twins


@pytest.mark.parametrize("name", _fixture_names())
def test_protocol_fixture(name):
    """Each fixture module fires (or stays silent on) exactly its RULE;
    silent twins additionally introduce NO P-series finding at all."""
    mod, path = _load_fixture(name)
    assert mod.EXPECT in ("fire", "silent"), name
    if mod.MODE == "ast":
        findings = [f for f in analyze_file(str(path))
                    if f.rule.startswith("P")]
    else:
        assert mod.MODE == "schedule", name
        spec, schedules = mod.build()
        findings = check_schedules(spec, schedules, entrypoint=name)
    fired = [f for f in findings if f.rule == mod.RULE]
    if mod.EXPECT == "fire":
        assert fired, f"{name}: {mod.RULE} did not fire ({findings})"
    else:
        assert not findings, f"{name}: expected silence, got {findings}"


def test_fixture_dir_covers_every_p_rule():
    """Coverage pin: each of P300–P304 has BOTH a fire and a silent
    twin, so a new P rule cannot land without its seeded evidence."""
    twins: dict = {}
    for name in _fixture_names():
        mod, _ = _load_fixture(name)
        twins.setdefault(mod.RULE, set()).add(mod.EXPECT)
    assert set(twins) == {"P300", "P301", "P302", "P303", "P304"}, twins
    for rule, kinds in twins.items():
        assert kinds == {"fire", "silent"}, (rule, kinds)


# ------------------------------------------------- re-mesh property


@pytest.mark.parametrize("surface_name", ["mpmd_drill", "mpmd_3stage"])
def test_every_single_slot_shrink_stays_protocol_clean(surface_name):
    """replace_pipeline over EVERY single-slot failure must produce a
    spec whose composed schedules are P300/P301-clean — the pre-launch
    gate can never veto a legitimate re-mesh."""
    spec = protocol_surface()[surface_name]
    assert analyze_pipeline(spec) == []
    for slot in range(spec.total_slots):
        shrunk, slot_map = replace_pipeline(spec, {slot})
        findings = analyze_pipeline(
            shrunk, entrypoint=f"{surface_name}:kill{slot}")
        bad = [f for f in findings if f.rule in ("P300", "P301")]
        assert not bad, (surface_name, slot, bad)
        assert slot not in slot_map


def test_simulation_is_exhaustive_on_surface():
    """Every (stage, rank) schedule on the repo surface is non-trivial:
    the model actually contains p2p frames, votes and collectives (a
    degenerate empty model would vacuously pass everything)."""
    for name, spec in sorted(protocol_surface().items()):
        schedules = build_schedules(spec)
        assert len(schedules) == spec.total_slots, name
        kinds = {e.kind for evs in schedules.values() for e in evs}
        if len(spec.stages) > 1:
            assert {"send", "recv"} <= kinds, (name, kinds)
        if any(st.dp > 1 for st in spec.stages):
            assert {"vote", "collective"} <= kinds, (name, kinds)


# --------------------------------------------- fixture stream model


@pytest.mark.parametrize("fixture", ["steady", "shrink_stage"])
def test_committed_fixture_streams_match_schedule_model(fixture):
    """Satellite 2: every replayed transfer event corresponds to a
    modeled act frame (edge, plan index, byte count) of the pipeline
    incarnation it ran under — goldens and checker cannot silently
    diverge."""
    path = REPO / "tests" / "mpmd_fixtures" / f"{fixture}.json"
    assert validate_fixture_events(path) == []


def test_tampered_fixture_stream_fires_p300():
    """Mutating a single replayed transfer line (wrong edge; wrong byte
    count) is caught against the schedule model."""
    from tpudml.mpmd.fixture import replay_fixture

    doc = json.loads(
        (REPO / "tests" / "mpmd_fixtures" / "steady.json").read_text())
    lines = replay_fixture(dict(doc))["lines"]

    def tamper(mutate):
        out = list(lines)
        for i, line in enumerate(out):
            ev = json.loads(line)
            if ev.get("event") == "transfer":
                mutate(ev)
                out[i] = json.dumps(
                    ev, sort_keys=True, separators=(",", ":"))
                break
        return out

    wrong_edge = validate_fixture_events(
        doc, lines=tamper(lambda ev: ev.update(edge="s9r9->s9r9")))
    assert any(f.rule == "P300" for f in wrong_edge), wrong_edge
    wrong_bytes = validate_fixture_events(
        doc, lines=tamper(lambda ev: ev.update(bytes=ev["bytes"] + 1)))
    assert any(f.rule == "P300" for f in wrong_bytes), wrong_bytes
    dropped = validate_fixture_events(
        doc,
        lines=[l for l in lines
               if json.loads(l).get("event") != "transfer"
               or json.loads(l).get("index") != 0
               or json.loads(l).get("step") != 0],
    )
    assert any("omitted modeled frame" in f.message for f in dropped), dropped


# ------------------------------------------------- traced signatures


def test_traced_collective_signatures_drive_p302():
    """collective_shape_signature extracts (op, axes, shape) from a real
    traced program, and injecting divergent per-rank signatures fires
    P302 while identical ones stay silent."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    import numpy as np

    from tpudml.analysis.protocol import traced_collective_events

    mesh = Mesh(np.asarray(jax.devices("cpu")[:2]), ("data",))

    def make(width):
        @jax.jit
        def fn(x):
            return shard_map(
                lambda v: jax.lax.psum(v, "data"), mesh=mesh,
                in_specs=P("data"), out_specs=P(),
            )(x)

        return traced_collective_events(fn, (jnp.ones((2, width)),))

    sig_a, sig_b = make(4), make(8)
    assert sig_a and sig_a[0][0] == "psum", sig_a
    assert sig_a != sig_b

    spec = protocol_surface()["mpmd_drill"]
    silent = check_schedules(
        spec, build_schedules(spec, stage_collectives={0: sig_a, 1: sig_a}))
    assert silent == [], silent
    mixed = build_schedules(
        spec, stage_collectives={(0, 0): sig_a, (0, 1): sig_b, 1: sig_a})
    fired = [f for f in check_schedules(spec, mixed) if f.rule == "P302"]
    assert len(fired) == 1, fired


# --------------------------------------------------------------- CLI


def _run_cli(*cli_args, timeout=180):
    return subprocess.run(
        [sys.executable, "-m", "tpudml.analysis", *cli_args],
        cwd=REPO, capture_output=True, text=True, timeout=timeout,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


def test_protocol_cli_strict_green_and_deterministic():
    """Satellite 4 (protocol slice): ``--protocol --strict`` exits 0
    with zero findings on the real surface, and the report is
    byte-deterministic across runs."""
    first = _run_cli("--protocol", "--strict")
    assert first.returncode == 0, first.stdout + first.stderr
    assert "0 finding(s)" in first.stdout
    second = _run_cli("--protocol", "--strict")
    assert second.stdout == first.stdout


def test_protocol_cli_json_names_surface():
    """--protocol --format json emits the machine shape with zero
    active findings, and the checked surface itself (drill + 3stage +
    the committed fixtures including their post-kill shrinks) is
    pinned."""
    names = set(protocol_surface())
    assert {"mpmd_drill", "mpmd_3stage", "fixture:steady",
            "fixture:shrink_stage",
            "fixture:shrink_stage:after_kill3"} <= names
    proc = _run_cli("--protocol", "--format", "json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout)
    assert set(out) == {"active", "allowed", "stale_allowlist"}
    assert out["active"] == []
    # Partial run: --protocol never judges allowlist staleness.
    assert out["stale_allowlist"] == []


def test_full_surface_findings_cover_protocol():
    """The default full run folds the protocol surface in (what
    --strict CI gates); here we pin the in-process equivalent."""
    assert analyze_protocol_surface() == []


# ------------------------------------------------- controller gate


def _controller(tmp_path, checker, cmd=None):
    from tpudml.launch.cluster import ClusterSpec
    from tpudml.mpmd.groups import MPMDController

    spec = protocol_surface()["mpmd_drill"]
    return MPMDController(
        cmd or [sys.executable, "-c", "pass"],
        spec,
        ClusterSpec(timeout_s=120.0),
        run_dir=tmp_path / "run",
        ckpt_dir=tmp_path / "ckpt",
        max_reforms=1,
        protocol_checker=checker,
        sink=open(os.devnull, "w"),
    )


def test_controller_refuses_rejected_spec(tmp_path):
    """A spec the checker rejects never spawns: no round records, a
    ``protocol_rejected`` stop reason, and machine-readable receipts in
    both the result and ``protocol_report.json``."""
    from tpudml.analysis.findings import Finding

    calls = []

    def reject(pipeline):
        calls.append(pipeline)
        return [Finding("P300", "injected asymmetry",
                        entrypoint="protocol:test")]

    ctl = _controller(
        tmp_path, reject,
        cmd=[sys.executable, "-c", "raise SystemExit(9)"])
    res = ctl.run()
    assert len(calls) == 1
    assert res.stop_reason == "protocol_rejected"
    assert res.records == [] and not res.success
    assert len(res.protocol) == 1 and res.protocol[0]["ok"] is False
    assert res.protocol[0]["findings"][0]["rule"] == "P300"
    assert res.to_dict()["protocol"] == res.protocol
    report = json.loads(
        (tmp_path / "run" / "protocol_report.json").read_text())
    assert report["ok"] is False
    assert report["checks"][0]["findings"][0]["severity"] == "error"
    # obs_report surfaces the verdict next to the MPMD section.
    sys.path.insert(0, str(REPO / "tools"))
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    text = obs_report.report(tmp_path)
    assert "protocol gate" in text and "REJECTED at round 0" in text


def test_controller_gate_passes_clean_spec_with_receipts(tmp_path):
    """The real checker on the drill spec: the pipeline launches (one
    trivially-exiting round), the receipt is recorded clean, and the
    report file says ok."""
    ctl = _controller(tmp_path, None)  # default = analyze_pipeline
    res = ctl.run()
    assert res.stop_reason == "success", res.stop_reason
    assert res.success and len(res.records) == 1
    assert [r["ok"] for r in res.protocol] == [True]
    assert res.protocol[0]["findings"] == []
    report = json.loads(
        (tmp_path / "run" / "protocol_report.json").read_text())
    assert report["ok"] is True and len(report["checks"]) == 1
    sys.path.insert(0, str(REPO / "tools"))
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    assert "protocol gate" in obs_report.report(tmp_path)
