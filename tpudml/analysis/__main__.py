"""CLI: ``python -m tpudml.analysis [--strict] [--cost] [...]``.

Report-only by default; ``--strict`` (the CI mode) exits non-zero when
any finding is not covered by the committed allowlist, and warns on
allowlist entries that matched nothing (stale suppressions). ``--cost``
switches to the static cost reports: a per-entrypoint comm/HBM table on
stdout plus ``analysis/cost_report.json`` for machines. ``--protocol``
runs only the cross-rank protocol pass (P300–P303 over the repo's
drill/fixture ``PipelineSpec`` surface plus the AST-hosted P304 port
lint) — jax-free, milliseconds, byte-deterministic; the same findings
are folded into the default full run, so ``--strict`` covers them.
``--format`` selects the findings output: ``text`` (human), ``json``,
or ``github`` (workflow-annotation lines). The jaxpr pass needs >= 2
visible devices, so an 8-device CPU host platform is provisioned before
the first backend touch — same dance as ``tests/conftest.py`` — which
makes the tool runnable on any dev box with ``JAX_PLATFORMS=cpu``, no
TPU required.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

COST_REPORT_PATH = os.path.join("analysis", "cost_report.json")

# --format github: one workflow-annotation line per finding, mapped from
# the rule severity (info → notice).
_GITHUB_LEVEL = {"error": "error", "warn": "warning", "info": "notice"}


def _provision_devices() -> None:
    """Force an 8-device CPU platform before jax initializes a backend."""
    try:
        # Repo harness helper (also recovers an already-latched backend).
        from __graft_entry__ import _provision_cpu_mesh

        _provision_cpu_mesh(8)
        return
    except Exception:
        pass
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _github_line(f) -> str:
    level = _GITHUB_LEVEL.get(f.severity, "warning")
    loc = ""
    if f.file:
        loc = f"file={f.file}"
        if f.line:
            loc += f",line={f.line}"
    ep = f" [{f.entrypoint}]" if f.entrypoint else ""
    # '::' inside the message would terminate the annotation early.
    msg = f"{f.rule}{ep}: {f.message}".replace("::", ":")
    return f"::{level} {loc}::{msg}"


def _finding_dicts(findings) -> list[dict]:
    return [f.__dict__ | {"severity": f.severity} for f in findings]


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tpudml.analysis",
        description="Static pre-flight analysis for TPU distributed "
                    "training hazards (jaxpr + AST + dataflow passes).",
    )
    parser.add_argument("--strict", action="store_true",
                        help="exit 1 on any finding not in the allowlist; "
                             "warn on stale allowlist entries")
    parser.add_argument("--format", default=None, dest="fmt",
                        choices=("text", "json", "github"),
                        help="findings output format (default: text)")
    parser.add_argument("--json", action="store_true", dest="as_json",
                        help="alias for --format json")
    parser.add_argument("--protocol", action="store_true",
                        help="cross-rank protocol pass only: P300-P303 "
                             "over the drill/fixture PipelineSpec surface "
                             "plus the AST P304 port-discipline lint "
                             "(no tracing, no device mesh)")
    parser.add_argument("--cost", action="store_true",
                        help="emit the static comm/HBM cost table and "
                             f"write {COST_REPORT_PATH}")
    parser.add_argument("--hbm_budget", type=float, default=None,
                        metavar="MB",
                        help="arm J116: flag entrypoints whose static "
                             "peak-live-buffer estimate exceeds this many "
                             "megabytes")
    parser.add_argument("--plan", default=None, metavar="PLAN_JSON",
                        help="arm J118: re-trace the plan's winning "
                             "entrypoint and flag traced comm/HBM that "
                             "deviates >10%% from its predicted block")
    parser.add_argument("--entrypoints", default=None, metavar="A,B",
                        help="comma-separated jaxpr entrypoints "
                             "(default: all; see --list-rules)")
    parser.add_argument("--paths", nargs="*", default=None,
                        help="roots for the AST pass "
                             "(default: tpudml tasks tools)")
    parser.add_argument("--allowlist", default=None, metavar="TOML",
                        help="allowlist path (default: "
                             "analysis/allowlist.toml)")
    parser.add_argument("--skip-jaxpr", action="store_true",
                        help="AST pass only (no tracing, no jax import)")
    parser.add_argument("--skip-ast", action="store_true",
                        help="jaxpr pass only")
    parser.add_argument("--show-allowed", action="store_true",
                        help="also print findings the allowlist suppressed")
    parser.add_argument("--list-rules", action="store_true",
                        help="print the rule catalogue and entrypoints")
    args = parser.parse_args(argv)
    fmt = args.fmt or ("json" if args.as_json else "text")

    from tpudml.analysis.findings import RULES, sort_findings

    if args.list_rules:
        from tpudml.analysis.entrypoints import ENTRYPOINTS

        for rule, (sev, desc) in RULES.items():
            print(f"{rule}  {sev:5s}  {desc}")
        print("\nentrypoints:", ", ".join(ENTRYPOINTS))
        return 0

    names = None
    if args.entrypoints:
        from tpudml.analysis.entrypoints import ENTRYPOINTS

        names = [n.strip() for n in args.entrypoints.split(",") if n.strip()]
        unknown = [n for n in names if n not in ENTRYPOINTS]
        if unknown:
            parser.error(f"unknown entrypoints {unknown}; "
                         f"known: {', '.join(ENTRYPOINTS)}")

    if args.cost:
        _provision_devices()
        from tpudml.analysis.cost import (
            build_cost_report,
            format_cost_table,
            write_cost_report,
        )
        from tpudml.analysis.entrypoints import cost_entrypoints

        costs, cost_findings = cost_entrypoints(names)
        os.makedirs(os.path.dirname(COST_REPORT_PATH), exist_ok=True)
        write_cost_report(costs, COST_REPORT_PATH)
        if fmt == "json":
            print(json.dumps(build_cost_report(costs), indent=2))
        else:
            print(format_cost_table(costs))
            print(f"\nwrote {COST_REPORT_PATH}")
        # Cost mode reports but does not gate: broken entrypoints still
        # surface (as J100 lines) so the table can't silently shrink.
        for f in sort_findings(cost_findings):
            print(f.format())
        return 1 if (args.strict and cost_findings) else 0

    hbm_budget_bytes = None
    if args.hbm_budget is not None:
        hbm_budget_bytes = int(args.hbm_budget * 1e6)

    findings = []
    if args.protocol:
        # Protocol-only mode: the schedule checks plus the P304 slice of
        # the AST pass — no tracing, no jax, byte-deterministic.
        from tpudml.analysis.ast_pass import analyze_tree
        from tpudml.analysis.protocol import analyze_protocol_surface

        roots = args.paths or [r for r in ("tpudml", "tasks", "tools")
                               if os.path.isdir(r)]
        findings.extend(analyze_protocol_surface())
        findings.extend(f for f in analyze_tree(roots)
                        if f.rule == "P304")
    else:
        if not args.skip_ast:
            from tpudml.analysis.ast_pass import analyze_tree

            roots = args.paths or [r for r in ("tpudml", "tasks", "tools")
                                   if os.path.isdir(r)]
            findings.extend(analyze_tree(roots))
        if not args.skip_jaxpr:
            _provision_devices()
            from tpudml.analysis.entrypoints import analyze_entrypoints

            findings.extend(analyze_entrypoints(names, hbm_budget_bytes))
        if not args.skip_ast and not args.skip_jaxpr:
            # Full runs also cover the protocol surface (cheap, jax-free)
            # so --strict gates P300-P303 alongside everything else.
            from tpudml.analysis.protocol import analyze_protocol_surface

            findings.extend(analyze_protocol_surface())
        if args.plan:
            _provision_devices()
            from tpudml.plan import load_plan, plan_drift_findings

            findings.extend(plan_drift_findings(load_plan(args.plan)))

    from tpudml.analysis.allowlist import (
        load_allowlist,
        split_allowed,
        unused_entries,
    )

    entries = load_allowlist(args.allowlist)
    active, allowed = split_allowed(sort_findings(findings), entries)
    # Stale-entry detection needs the full finding surface: a filtered
    # run (subset of entrypoints/paths, or a skipped pass) legitimately
    # misses findings its allowlist entries cover.
    full_run = (not args.protocol and names is None and args.paths is None
                and not args.skip_jaxpr and not args.skip_ast)
    stale = unused_entries(findings, entries) if full_run else []

    if fmt == "json":
        print(json.dumps({
            "active": _finding_dicts(active),
            "allowed": _finding_dicts(allowed),
            "stale_allowlist": [e.__dict__ for e in stale],
        }, indent=2))
    elif fmt == "github":
        for f in active:
            print(_github_line(f))
        for e in stale:
            print(f"::warning file={os.path.join('analysis', 'allowlist.toml')}"
                  f"::stale allowlist entry rule={e.rule} path={e.path} "
                  f"matched no finding ({e.reason})")
    else:
        for f in active:
            print(f.format())
        if args.show_allowed and allowed:
            print(f"\n-- allowlisted ({len(allowed)}) --")
            for f in allowed:
                print(f.format())
        if args.strict and stale:
            print(f"\n-- stale allowlist entries ({len(stale)}) --")
            for e in stale:
                print(f"  {e.rule} path={e.path!r}"
                      + (f" line={e.line}" if e.line else "")
                      + f" — matched no finding (reason was: {e.reason})")
        print(f"\n{len(active)} finding(s), {len(allowed)} allowlisted "
              f"({len(entries)} allowlist entr{'y' if len(entries) == 1 else 'ies'})")

    if args.strict and active:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
