"""SLO-aware admission: price a decode step before occupying a slot.

PR 9's overload guard is purely *queue-shaped* (``max_queue`` bounds the
line, ``deadline_s`` drops the hopeless); it admits whenever a slot is
free, even when the marginal occupant pushes every tenant's per-token
cadence past its latency contract. This module adds the missing price
tag, built on the same roofline inputs as the PR 10 static cost reports:
a decode step streams the weights once plus each active slot's KV window
from HBM, and (under tensor parallelism) moves two activation allreduces
per block over the interconnect, priced with the shared ring model
(``comm.timing.collective_wire_bytes``). The scheduler then admits the
queue head only while

    predicted_step_seconds(active + 1) <= slo.tpot_budget_s

deferring it (event ``("defer", rid, -1, step)``) otherwise — FIFO order
and the (arrival, rid) tie-break are preserved because admission only
ever peeks the head; nobody overtakes. An idle engine always admits, so
a budget that is simply unsatisfiable degrades to slots=1 behaviour
instead of deadlocking the queue.

Honesty note (also in docs/API.md): the engine's compiled step runs ALL
slots every step, so on real hardware the measured step time is nearly
flat in occupancy — the model prices the *work* a step does, which is
what the TPOT contract cares about at production batch sizes, and what
makes admission deterministic on the CPU-dryrun virtual clock.
"""

from __future__ import annotations

from dataclasses import dataclass

from tpudml.capabilities import reject
from tpudml.comm.timing import collective_wire_bytes

_CACHE_ITEMSIZE = {"f32": 4, "bf16": 2, "int8": 1, "bf16_sim": 4, "int8_sim": 4}

# Stored bytes per PARAMETER element, keyed by ServeConfig.weight_quant.
# Same convention as the cache table above: the "_sim" oracle keeps f32
# storage (it only rounds values), so it prices like f32 — pricing the
# sim as if it saved bytes would be the dishonest-placement bug the
# fleet router's SLO pricing exists to avoid.
_PARAM_ITEMSIZE = {None: 4, "f32": 4, "bf16": 2, "int8": 1, "int8_sim": 4}


@dataclass(frozen=True)
class SLOConfig:
    """Latency contract + machine constants for admission pricing.

    ``tpot_budget_s``: target per-token cadence (time-per-output-token)
    the tier promises every admitted tenant. ``hbm_gbps``/``ici_gbps``:
    memory and interconnect roofline constants, same role as the PR 10
    ``--cost`` report's; defaults are deliberately round CPU-dryrun
    stand-ins — rerun with chip constants for real capacity planning."""

    tpot_budget_s: float
    hbm_gbps: float = 100.0
    ici_gbps: float = 45.0

    def __post_init__(self):
        if self.tpot_budget_s <= 0:
            raise ValueError("tpot_budget_s must be > 0")
        if self.hbm_gbps <= 0 or self.ici_gbps <= 0:
            raise ValueError("hbm_gbps/ici_gbps must be > 0")


class DecodeCostModel:
    """Static per-step cost of the serving engine's decode program.

    bytes(step) = params_read + n_active × (per_slot_window + logits_tail)
                  + spec_draft
    seconds(step) = bytes/hbm + ring_wire_bytes/ici

    The per-slot window is what the cache layout decides: the dense
    engine streams ``max_len`` rows per slot; the paged engine gathers
    exactly the slot's ``max_pages`` table rows (``max_pages ×
    page_size`` positions) — gathering the whole pool instead is the
    J117 anti-pattern and would show up here as a pool-sized window.
    Spec decode adds K draft passes (draft weights re-read per drafted
    token) but amortizes the whole step over ~``1 + accepted`` emitted
    tokens; admission prices the pessimistic 1-token floor."""

    def __init__(self, model, cfg, slo: SLOConfig, *, world: int = 1,
                 draft_model=None):
        if getattr(model, "stateful", False):
            # This model prices a GPT-shaped block (embed_dim // num_heads
            # heads, a 4x MLP, K/V rows only): not a pattern model's
            # experts and recurrent state.
            reject("serve_pattern_slo")
        self.slo = slo
        self.world = world
        kv_heads = model.num_kv_heads or model.num_heads
        head_dim = model.embed_dim // model.num_heads
        itemsize = _CACHE_ITEMSIZE[cfg.cache_kind]
        if cfg.cache_layout == "paged":
            window_rows = cfg.max_pages * cfg.page_size
        else:
            window_rows = cfg.max_len
        # K + V rows across all layers, once per step per active slot.
        self.per_slot_bytes = (
            2 * window_rows * kv_heads * head_dim * itemsize * model.num_layers
        )
        p_item = _PARAM_ITEMSIZE[getattr(cfg, "weight_quant", None)]
        self.params_bytes = (
            self._params_bytes(model, itemsize=p_item) // max(world, 1)
        )
        self.draft_bytes = 0
        self.spec_k = cfg.spec_k or 0
        if draft_model is not None and self.spec_k:
            self.draft_bytes = (
                self._params_bytes(draft_model, itemsize=p_item)
                // max(world, 1)
            )
        # Decode tail: the unfused step writes each slot's [vocab] logits
        # row to HBM and reads it back for the argmax + stats pass; the
        # fused head (ops/decode_head.py) keeps the row in VMEM tiles, so
        # its tail traffic is zero. Priced per slot so admission sees the
        # fused tail's headroom at production vocab sizes.
        if getattr(cfg, "fused_head", False):
            self.tail_bytes_per_slot = 0
        else:
            self.tail_bytes_per_slot = 2 * model.vocab_size * 4
        # Two activation allreduces per block per step under TP (attn.out
        # + mlp.fc2 — serve/tp.py), priced on the shared ring model.
        act_bytes = model.embed_dim * 4
        self.wire_bytes_per_slot = (
            2 * model.num_layers
            * collective_wire_bytes("psum", act_bytes, world)
        )

    @staticmethod
    def _params_bytes(model, *, itemsize: int = 4) -> int:
        """Stored parameter bytes at ``itemsize`` bytes/element — the ONE
        param-pricing code path for every weight dtype (f32/bf16/int8):
        quantization changes the multiplier, never the element count."""
        d, v, l = model.embed_dim, model.vocab_size, model.num_layers
        kv = model.num_kv_heads or model.num_heads
        head_dim = d // model.num_heads
        mlp = getattr(model, "mlp_ratio", 4) * d
        per_block = d * d * 2 + d * kv * head_dim * 2 + 2 * d * mlp
        return itemsize * (v * d * 2 + l * per_block)  # embed+head+blocks

    def step_seconds(self, n_active: int) -> float:
        hbm = (
            self.params_bytes
            + self.spec_k * self.draft_bytes
            + n_active * (self.per_slot_bytes + self.tail_bytes_per_slot)
        )
        wire = n_active * self.wire_bytes_per_slot
        return (
            hbm / (self.slo.hbm_gbps * 1e9)
            + wire / (self.slo.ici_gbps * 1e9)
        )

    def admit_ok(self, n_active: int) -> bool:
        """May the scheduler add one more tenant? Always yes from idle
        (the budget can defer, never deadlock)."""
        if n_active == 0:
            return True
        return self.step_seconds(n_active + 1) <= self.slo.tpot_budget_s
