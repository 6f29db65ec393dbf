"""Static-analysis suite: pre-flight lint for TPU distributed training.

Two complementary passes over the codebase (docs/ANALYSIS.md has the
full rule catalogue):

- the **jaxpr pass** (``jaxpr_pass``, rules J1xx) traces the real train
  steps — the engines in ``tpudml/parallel/`` wired to tiny models by
  ``entrypoints`` — with abstract inputs on CPU and walks the resulting
  ClosedJaxpr for hazards that otherwise only fail on a multi-host
  slice: unbound collective axes, branch-divergent collectives, host
  callbacks, stray bf16→f32 upcasts, closure-captured megabyte
  constants, undonated training state;
- the **AST pass** (``ast_pass``, rules A2xx) lints the source for
  hazards tracing cannot see: Python control flow over traced values,
  PRNG key reuse, epoch loops missing ``set_epoch``, host-clock timing
  without ``block_until_ready``;
- the **dataflow pass** (``dataflow``, rules J112–J116) abstractly
  interprets the same traced programs under a per-(value, mesh-axis)
  replication lattice — missing psums under ``check_vma=False``,
  shard-dependent while trip counts around collectives, donated-buffer
  reuse, allreduce-then-shard waste — and feeds the static comm/HBM
  cost reports in ``cost`` (``--cost`` / ``analysis/cost_report.json``);
- the **protocol pass** (``protocol``, rules P300–P304) models every
  (stage, rank) of the MPMD pipeline as an ordered schedule of blocking
  events (p2p frames, drain votes, stage-group collectives) and checks
  the *composed* system for boundary asymmetry, cross-rank deadlock,
  collective-sequence divergence and vote-before-collective ordering —
  jax-free, so ``MPMDController`` runs it as a pre-launch gate
  (``--protocol`` on the CLI; P304, the port-discipline lint, rides in
  the AST pass).

Run it as ``python -m tpudml.analysis`` (``--strict`` for CI, paired
with the committed ``analysis/allowlist.toml``).
"""

from tpudml.analysis.allowlist import (
    load_allowlist,
    split_allowed,
    unused_entries,
)
from tpudml.analysis.ast_pass import analyze_file, analyze_source, analyze_tree
from tpudml.analysis.cost import (
    EntrypointCost,
    build_cost_report,
    check_hbm_budget,
    format_cost_table,
    peak_live_bytes,
    summarize_cost,
    write_cost_report,
)
from tpudml.analysis.dataflow import (
    CommEvent,
    DataflowResult,
    analyze_dataflow,
)
from tpudml.analysis.entrypoints import (
    ENTRYPOINTS,
    analyze_entrypoint,
    analyze_entrypoints,
    cost_entrypoints,
)
from tpudml.analysis.findings import RULES, Finding, sort_findings
from tpudml.analysis.jaxpr_pass import (
    analyze_callable,
    analyze_closed_jaxpr,
    collective_shape_signature,
    donation_findings,
)
from tpudml.analysis.protocol import (
    Ev,
    analyze_pipeline,
    analyze_protocol_surface,
    build_schedules,
    check_schedules,
    protocol_surface,
    traced_collective_events,
    validate_fixture_events,
)

__all__ = [
    "RULES",
    "CommEvent",
    "DataflowResult",
    "EntrypointCost",
    "Ev",
    "Finding",
    "ENTRYPOINTS",
    "analyze_callable",
    "analyze_closed_jaxpr",
    "analyze_dataflow",
    "analyze_entrypoint",
    "analyze_entrypoints",
    "analyze_file",
    "analyze_pipeline",
    "analyze_protocol_surface",
    "analyze_source",
    "analyze_tree",
    "build_cost_report",
    "build_schedules",
    "check_schedules",
    "collective_shape_signature",
    "protocol_surface",
    "traced_collective_events",
    "validate_fixture_events",
    "check_hbm_budget",
    "cost_entrypoints",
    "donation_findings",
    "format_cost_table",
    "load_allowlist",
    "peak_live_bytes",
    "sort_findings",
    "split_allowed",
    "summarize_cost",
    "unused_entries",
    "write_cost_report",
]
