"""CLI: ``python -m tpudml.plan [--world N] [--out plan.json] [...]``.

Emits the winning candidate as a runnable ``plan.json`` (v1 schema) and
prints the ranked candidate table.  ``--format`` follows the analysis
CLI contract: ``text`` (human table), ``json`` (the full plan),
``github`` (workflow-annotation lines — ``notice`` for the winner,
``warning`` per demoted candidate, ``error`` when planning fails).
``--check`` is the CI smoke: plan the flagship spec at world 4 and 8,
require a verified winner at both, write nothing.

The self-verification trace needs >= 2 visible devices, so an 8-device
CPU host platform is provisioned before the first backend touch — the
same dance as ``python -m tpudml.analysis`` — making the planner
runnable on any dev box, no TPU required.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

PLAN_OUT_PATH = os.path.join("analysis", "plan.json")


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


def _spec_from_args(args):
    from tpudml.plan.space import ModelSpec, flagship_lm

    if args.spec:
        with open(args.spec) as fh:
            return ModelSpec.from_dict(json.load(fh))
    return flagship_lm()


def _fmt_row(rank: int, entry: dict) -> str:
    c, s = entry["candidate"], entry["score"]
    return (f"{rank:3d}  {s['per_token_s']:.3e}  {s['step_time_s']:.3e}  "
            f"{s['exposed_comm_s']:.2e}  {s['est_hbm_bytes']:>12d}  "
            f"{c['key']}")


def _print_text(plan: dict, top: int) -> None:
    w = plan["winner"]
    print(f"plan v{plan['version']}  world={plan['world']}  "
          f"spec={plan['spec']['embed_dim']}d/"
          f"{plan['spec']['num_layers']}L/"
          f"{plan['spec']['num_heads']}h/v{plan['spec']['vocab_size']}")
    print(f"winner: {w['candidate']['key']}")
    ver = plan["verification"]
    print(f"verified: entrypoint={ver['entrypoint']} ok={ver['ok']} "
          f"findings={len(ver['findings'])} demoted={len(ver['demoted'])}")
    print(f"predicted: comm_wire_bytes={plan['predicted']['comm_wire_bytes']:.0f} "
          f"peak_hbm_bytes={plan['predicted']['peak_hbm_bytes']}")
    print(f"\nrank  per_token_s  step_time_s  exposed_s   est_hbm_bytes"
          f"  candidate")
    for i, entry in enumerate(plan["ranking"][:top], 1):
        print(_fmt_row(i, entry))
    shown = min(top, len(plan["ranking"]))
    print(f"\n{len(plan['ranking'])} ranked ({shown} shown), "
          f"{len(plan['pruned'])} pruned")
    if plan["pruned"]:
        by_rule: dict = {}
        for r in plan["pruned"]:
            by_rule[r["rule"]] = by_rule.get(r["rule"], 0) + 1
        for rule in sorted(by_rule):
            print(f"  {by_rule[rule]:4d}  {rule}")


def _print_github(plan: dict) -> None:
    # Same annotation grammar as ``python -m tpudml.analysis --format
    # github``: '::' inside a message would end the annotation early.
    def msg(s: str) -> str:
        return s.replace("::", ":")

    w = plan["winner"]
    print(f"::notice ::PLAN[world={plan['world']}]: winner "
          + msg(w["candidate"]["key"])
          + f" per_token_s={w['score']['per_token_s']:.3e}")
    for d in plan["verification"]["demoted"]:
        rules = ",".join(sorted({f["rule"] for f in d["findings"]}))
        print(f"::warning ::PLAN[world={plan['world']}]: demoted "
              + msg(d["candidate"]["key"]) + f" ({rules})")


def _check(parser) -> int:
    """CI smoke: verified winner at world 4 and 8 on the flagship spec."""
    from tpudml.plan.emit import make_plan
    from tpudml.plan.space import flagship_lm

    spec = flagship_lm()
    failures = 0
    for world in (4, 8):
        try:
            plan = make_plan(spec, world)
        except Exception as exc:  # noqa: BLE001 — CI smoke reports, never raises
            print(f"::error ::PLAN[world={world}]: {exc}")
            failures += 1
            continue
        ver = plan["verification"]
        ok = ver["ok"] and not ver["demoted"]
        status = "ok" if ok else "FAIL"
        print(f"plan --check world={world}: {status} winner="
              f"{plan['winner']['candidate']['key']} "
              f"findings={len(ver['findings'])} demoted={len(ver['demoted'])}")
        if not ok:
            failures += 1
    return 1 if failures else 0


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tpudml.plan",
        description="Static autosharding planner: enumerate, prune, "
                    "score, and emit a verified runnable plan.json.",
    )
    parser.add_argument("--world", type=int, default=8,
                        help="chip count to plan for (default: 8)")
    parser.add_argument("--spec", default=None, metavar="JSON",
                        help="ModelSpec json file (default: the dryrun "
                             "flagship LM)")
    parser.add_argument("--hbm_budget", type=float, default=None,
                        metavar="MB",
                        help="prune candidates whose static peak-live "
                             "estimate exceeds this many megabytes (and "
                             "arm J116 on the verification trace)")
    parser.add_argument("--engines", default=None, metavar="A,B",
                        help="restrict the engine chains enumerated "
                             "(default: all)")
    parser.add_argument("--out", default=PLAN_OUT_PATH, metavar="PATH",
                        help=f"plan.json output path (default: "
                             f"{PLAN_OUT_PATH}; '-' to skip writing)")
    parser.add_argument("--format", default="text", dest="fmt",
                        choices=("text", "json", "github"),
                        help="stdout format (default: text)")
    parser.add_argument("--top", type=int, default=10,
                        help="ranked-table rows to print (default: 10)")
    parser.add_argument("--no-verify", action="store_true",
                        help="skip the trace + J112-J116 verification "
                             "(plan carries analytic estimates instead)")
    parser.add_argument("--check", action="store_true",
                        help="CI smoke: plan the flagship spec at world "
                             "4 and 8, exit non-zero unless both verify")
    args = parser.parse_args(argv)

    _provision_devices()
    if args.check:
        return _check(parser)

    from tpudml.plan.emit import make_plan, plan_to_json

    engines = None
    if args.engines:
        engines = [e.strip() for e in args.engines.split(",") if e.strip()]
    hbm_budget_bytes = None
    if args.hbm_budget is not None:
        hbm_budget_bytes = int(args.hbm_budget * 1e6)

    try:
        plan = make_plan(
            _spec_from_args(args),
            args.world,
            hbm_budget_bytes=hbm_budget_bytes,
            engines=engines,
            verify=not args.no_verify,
        )
    except (RuntimeError, ValueError) as exc:
        if args.fmt == "github":
            print(f"::error ::PLAN[world={args.world}]: {exc}")
        else:
            print(f"planning failed: {exc}", file=sys.stderr)
        return 1

    if args.out != "-":
        out_dir = os.path.dirname(args.out)
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
        with open(args.out, "w") as fh:
            fh.write(plan_to_json(plan))

    if args.fmt == "json":
        print(plan_to_json(plan), end="")
    elif args.fmt == "github":
        _print_github(plan)
    else:
        _print_text(plan, args.top)
        if args.out != "-":
            print(f"\nwrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
