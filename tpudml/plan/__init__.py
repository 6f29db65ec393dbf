"""Static autosharding planner: enumerate → prune → score → emit.

Closes the loop ROADMAP item 3 describes: PR 10's dataflow cost model
(``tpudml/analysis``) can price any traced program — this package turns
that reporter into a *decider*.  Given a :class:`~tpudml.plan.space.ModelSpec`
and a chip count it

1. **enumerates** the candidate space (``space.py``): mesh factorization
   × engine chain {DP, ZeRO-1, FSDP, TP, FSDP×TP, PP×DP} × zero1-overlap
   × accumulation × fused-kernel / sentinel / obs knobs;
2. **prunes** statically (``prune.py``): divisibility of heads / vocab /
   layers against the axis sizes, HBM over budget via the same peak-live
   estimate J116 uses, and every engine composition rejection through the
   shared capability table (``tpudml.capabilities``) the engines
   themselves raise from — planner and runtime cannot disagree;
3. **scores** survivors (``score.py``) on the shared ring wire model
   (``tpudml.comm.timing.collective_wire_bytes``) plus a roofline
   step-time estimate (compute FLOPs vs MXU, memory traffic vs HBM,
   exposed comm after overlap attribution);
4. **emits** the winner (``emit.py``) as a runnable ``plan.json`` (v2
   schema; v1 files still load) — and self-verifies it first: the
   winning engine is built on the dryrun mesh, traced, and run through
   the J112–J116 dataflow rules; a plan that would lose a psum or blow
   the HBM budget is rejected before it ever runs, and the traced
   comm/HBM land in the plan's ``predicted`` block, which rule J118
   later holds the code to.

Since PR 16 the planner is also a *runtime* controller: on an elastic
membership change ``tpudml.elastic.replan.Replanner`` re-runs this
pipeline at the new world size (recording receipts for why the old
config lost), and a J118/drift firing re-scores the lattice with the
measured constants folded in as a :class:`~tpudml.plan.score.Calibration`
— both land in the plan's v2 ``replan`` / ``calibration`` blocks.

CLI: ``python -m tpudml.plan`` (``--format text|json|github``,
``--check`` for the world-4/8 smoke).  Validation the other way — the
planner's top-1 against the measured best — waits for a four-chip cell
of the benchmark (ROADMAP.md A7): not measured.
"""

from tpudml.plan.emit import (
    PLAN_VERSION,
    SUPPORTED_PLAN_VERSIONS,
    build_candidate,
    load_plan,
    make_plan,
    plan_drift_findings,
    plan_to_json,
    verify_candidate,
)
from tpudml.plan.prune import PruneRecord, prune
from tpudml.plan.score import Calibration, Hardware, Score, score_candidate
from tpudml.plan.space import (
    Candidate,
    ModelSpec,
    enumerate_candidates,
    flagship_lm,
)

__all__ = [
    "PLAN_VERSION",
    "SUPPORTED_PLAN_VERSIONS",
    "Calibration",
    "Candidate",
    "Hardware",
    "ModelSpec",
    "PruneRecord",
    "Score",
    "build_candidate",
    "enumerate_candidates",
    "flagship_lm",
    "load_plan",
    "make_plan",
    "plan_drift_findings",
    "plan_to_json",
    "prune",
    "score_candidate",
    "verify_candidate",
]
