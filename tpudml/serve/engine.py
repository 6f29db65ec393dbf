"""Prefill–decode serving engine with continuous batching.

The execution model, in one sentence: a fixed decode batch of
``slots`` cache rows runs ONE jitted single-token decode step forever,
and the host-side scheduler rewrites rows — evicting finished sequences
and prefilling queued ones into the freed rows — between steps, so
request churn never triggers a recompile.

- **Decode** is ``TransformerLM.apply_decode`` under a donated jit: all
  slots advance one token per step at their OWN positions (``pos`` [B]),
  greedy argmax picks the next token. The jit is wrapped in a NAMED
  inner jit (``SERVE_DECODE_MARKER``) so analysis rule J110 can prove
  the program attends O(cache) per token — a decode-marked program that
  recomputes full-sequence attention per emitted token is exactly what
  the rule flags.
- **Prefill** fills a slot's cache in fixed-size chunks
  (``prefill_chunk`` tokens per program) via ``apply_prefill``: one
  compiled program per chunk INDEX, shared by every request and slot
  (the slot id is a traced scalar), so a max_len-M cache needs at most
  M/C prefill programs ever. The prompt's last token is NOT prefilled —
  it feeds the first decode step, which emits the first generated token.
- **The loop** (``ServingEngine.run``) keeps one decode step in flight:
  a pass dispatches step N + 1 and only then fetches and commits step N,
  the next token staying on the device (``_with_device_tokens``), so the
  device always has its next program queued and the host's work runs
  under a running step. Where the next step's inputs are data
  (speculative) or the commit's bookkeeping (paged) it fetches first.
- **Scheduling** is FIFO by arrival time with slot-index tie-breaking:
  deterministic under a fixed workload seed (the scheduler unit tests
  pin eviction/refill order), and starvation-free — an admitted request
  runs to completion, and the queue head is always the oldest
  unadmitted arrival.

Stale cache rows need no zeroing on eviction: a slot's attention mask is
``k_pos <= pos``, and every position is written before it is first
unmasked, so a new occupant can never read its predecessor's K/V. A
recurrent state has no such mask: for a ``stateful`` model
(``tpudml.models.hybrid``) admission zeroes the slot's state, prefill is
told how many tokens of a padded chunk are real, and the decode step is
told which slots are active (``make_stateful_decode_step``).

Three multi-tenant levers compose on top, each flag-gated in
``ServeConfig`` and each greedy-parity-exact against the dense path:
``cache_layout="paged"`` (+ ``prefix_sharing``) swaps the cache for a
page pool behind a slot→page table (serve/paged.py), ``spec_k>0`` swaps
the decode step for draft-then-verify speculative decoding
(serve/spec.py), and ``slo`` prices admission with the static cost model
(serve/sched.py). TP × {paged, spec} raises ServeCompositionError.
"""

from __future__ import annotations

import math
import time
from collections import deque
from dataclasses import dataclass, field
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from tpudml.capabilities import CompositionError, reject
from tpudml.obs.passlog import pass_log
from tpudml.obs.tracer import span
from tpudml.ops.decode_head import fused_decode_head, fused_decode_head_int8
from tpudml.serve.cache import KINDS, decode_kernel, row_scatter
from tpudml.serve.load import Request
from tpudml.serve.paged import PAGED_DECODE_MARKER, PagePool
from tpudml.serve.sched import DecodeCostModel, SLOConfig
from tpudml.serve.spec import draft_from_trunk, make_spec_decode_step


class ServeCompositionError(CompositionError):
    """Raised when serving levers are combined in a regime this tier has
    no correct compiled path for (today: tensor parallelism × paged
    cache, and tensor parallelism × speculative decoding). Loud by
    contract — the alternative is a silently wrong answer path."""

# Decode programs are jitted under this NAME so the call survives as a
# recognizably-named pjit equation in any traced program — the marker
# analysis rule J110 keys on. Mirrored as a string literal in
# tpudml/analysis/jaxpr_pass.py (pinned by test_analysis); XLA inlines
# inner jits at lowering, so the marker costs nothing on the chip.
SERVE_DECODE_MARKER = "_serve_decode_step"


def make_decode_step(model):
    """The one jitted decode program: (params, caches, tokens [B],
    pos [B]) → (next greedy tokens [B], logits [B, V], updated caches).
    Caches are donated — the engine rebinds them every step. The run
    loop only ever pulls the tokens to host; the logits output exists
    for the parity tests (and stays device-side, costing nothing)."""

    def _serve_decode_step(params, caches, tokens, pos):
        logits, caches = model.apply_decode(params, caches, tokens, pos)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits, caches

    inner = jax.jit(_serve_decode_step)

    def step(params, caches, tokens, pos):
        return inner(params, caches, tokens, pos)

    return jax.jit(step, donate_argnums=(1,))


def make_stateful_decode_step(model):
    """:func:`make_decode_step` for a ``stateful`` model (one with per-slot
    state no attention mask hides, `tpudml.models.hybrid`): (params, caches,
    state int32 [3, B] = tokens, positions, active 0/1) -> (next tokens [B]
    followed by the model's counters and its routes, logits [B, V], updated
    caches). Slots that are not active keep their state and take part in no
    expert's batch. ``model.counter_names`` names the int32 scalars over
    active slots that ride behind the tokens, and ``model.route_width`` the
    int32 values a slot behind those (every slot's expert choices at this
    token), so that the run loop sends one array and fetches one (the host's
    share of a 25 ms pass is what makes its tail noisy; PERF.md §6, PR 30).
    The counters go on ``serve/commit``, the routes into
    ``RequestStats.routes``."""

    def _serve_decode_step(params, caches, state):
        logits, caches, counters, routes = model.apply_decode(
            params, caches, state[0], state[1], state[2] != 0)
        packed = jnp.concatenate([
            jnp.argmax(logits, axis=-1).astype(jnp.int32),
            jnp.stack([counters[k] for k in model.counter_names]),
            routes.astype(jnp.int32).reshape(-1)])
        return packed, logits, caches

    assert _serve_decode_step.__name__ == SERVE_DECODE_MARKER
    inner = jax.jit(_serve_decode_step)

    def step(params, caches, state):
        return inner(params, caches, state)

    return jax.jit(step, donate_argnums=(1,))


def make_fused_decode_step(model, head_q=None, head_scale=None):
    """The fused-tail twin of :func:`make_decode_step`: the trunk runs to
    post-``ln_f`` features (``apply_decode_features``) and the head
    matmul, greedy pick, and step stats fold into ONE vocab-tiled Pallas
    program (ops/decode_head.py) — the [slots, vocab] logits row never
    round-trips HBM. Returns (next tokens [B], {"max_logit": [B],
    "lse": [B]}, caches): same arity as the unfused step (the run loop
    pulls tokens only), with the in-graph stats replacing the logits
    output as the step's observable. With ``head_q``/``head_scale`` set
    (int8 mode), the kernel consumes the int8 codes + scales directly,
    dequantizing per vocab tile in the oracle's exact op order — the
    dequantized f32 head never exists in HBM either."""

    def _serve_decode_step(params, caches, tokens, pos):
        h, caches = model.apply_decode_features(params, caches, tokens, pos)
        bias = params["head"].get("bias")
        if head_q is not None:
            tok, mx, lse = fused_decode_head_int8(h, head_q, head_scale, bias)
        else:
            tok, mx, lse = fused_decode_head(h, params["head"]["kernel"], bias)
        return tok, {"max_logit": mx, "lse": lse}, caches

    assert _serve_decode_step.__name__ == SERVE_DECODE_MARKER
    inner = jax.jit(_serve_decode_step)

    def step(params, caches, tokens, pos):
        return inner(params, caches, tokens, pos)

    return jax.jit(step, donate_argnums=(1,))


def make_paged_decode_step(model):
    """The paged twin of :func:`make_decode_step`: (params, pools,
    table [B, max_pages], tokens [B], pos [B]) → (next tokens [B],
    logits [B, V], updated pools). The table is an ordinary traced
    argument — page alloc/free between steps never recompiles — and the
    pools are donated. Jitted under its OWN marker name so analysis
    rule J117 (full-pool gather per token) can key on exactly the
    programs that read through a page table."""

    def _serve_paged_decode_step(params, caches, table, tokens, pos):
        logits, caches = model.apply_decode_paged(
            params, caches, table, tokens[:, None], pos
        )
        logits = logits[:, 0, :]
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits, caches

    assert _serve_paged_decode_step.__name__ == PAGED_DECODE_MARKER
    inner = jax.jit(_serve_paged_decode_step)

    def step(params, caches, table, tokens, pos):
        return inner(params, caches, table, tokens, pos)

    return jax.jit(step, donate_argnums=(1,))


def _with_device_tokens(decode, slots: int, stateful: bool):
    """What the run loop calls where it keeps a step in flight: ``decode``
    (whatever :func:`make_decode_step`, :func:`make_stateful_decode_step`,
    :func:`make_fused_decode_step` or the tensor-parallel engine returned,
    signature untouched) behind a select, in one donated jit that the
    device trace still calls ``jit_step``: (params, caches, prev, ctl int32
    [4, B] = the host's token, position, active 0/1, fresh 0/1) -> what
    ``decode`` returns. ``prev`` is the previous step's first output, still
    on the device (its first ``slots`` entries are the tokens): a slot takes
    its token from there, so step N + 1 can be queued before the host has
    seen step N, unless it is ``fresh`` (admitted since that step, or there
    was none), when the host's token stands. ``ctl`` is one NumPy array: one
    transfer, no program of its own between two steps."""

    def step(params, caches, prev, ctl):
        tokens = jnp.where(ctl[3] != 0, ctl[0], prev[:slots])
        if stateful:
            return decode(params, caches, jnp.stack([tokens, ctl[1], ctl[2]]))
        return decode(params, caches, tokens, ctl[1])

    return jax.jit(step, donate_argnums=(1,))


@dataclass(frozen=True)
class ServeConfig:
    """Engine shape knobs (all static — they size the compiled programs)."""

    slots: int = 4  # fixed decode batch: concurrent in-flight sequences
    max_len: int = 256  # cache rows per slot (prompt + generation bound)
    prefill_chunk: int = 32
    cache_kind: str = "f32"  # f32 | bf16 | int8 (serve.cache)
    eos_token: int | None = None  # early-stop token id (None: run budget out)
    # Overload guard. ``max_queue`` bounds the waiting line: an arrival
    # finding it full is REJECTED at admission control (event
    # ``("reject", rid, -1, step)``) instead of growing an unbounded
    # backlog whose tail latencies are all ruined together. ``deadline_s``
    # is a per-request TTL from its arrival: a queued request strictly
    # past its deadline is dropped before admission, an in-flight one is
    # evicted at the next decode-step boundary (both logged as
    # ``("expire", rid, slot, step)`` with slot=-1 for queued) — its
    # ``finished`` stays None, so it never pollutes the latency
    # percentiles of requests that met their contract.
    max_queue: int | None = None  # None: unbounded (pre-guard behaviour)
    deadline_s: float | None = None  # None: requests never expire
    # Virtual clock: with ``step_time_s`` set, "now" is
    # ``decode_steps × step_time_s`` (+ idle skips to the next arrival)
    # instead of the wall clock, so queue depth, rejections, and expiries
    # become a pure function of (workload seed, config) — the regime the
    # overload tests pin bit-for-bit.
    step_time_s: float | None = None
    # Cache layout. "dense" is the PR 8 [slots, max_len] block; "paged"
    # stores K/V in a fixed pool of [num_pages, page_size, ...] pages
    # addressed through a [slots, max_pages] table (serve/paged.py) —
    # max_len still bounds prompt + generation per request, but HBM is
    # sized by ``num_pages``, so short requests stop stranding long
    # requests' headroom. ``num_pages=None`` sizes the pool to dense
    # capacity + the garbage page (a pure-layout A/B at equal HBM).
    cache_layout: str = "dense"
    page_size: int = 16
    num_pages: int | None = None
    # Prefix sharing (paged only): admit-time page reuse for equal
    # prompt heads, refcounted, copy-on-write at the first divergent
    # page. Requires page_size % prefill_chunk == 0 so a shared head
    # always ends on a prefill-chunk boundary.
    prefix_sharing: bool = False
    # Speculative decoding: draft spec_k tokens per target step, exact
    # greedy acceptance-rejection (serve/spec.py). 0 disables. Admission
    # reserves spec_k rows of headroom per slot (the verify window
    # writes up to spec_k rows past the commit point).
    spec_k: int = 0
    # SLO-aware admission: with an SLOConfig set, the queue head is
    # admitted only while the priced decode step (serve/sched.py) fits
    # the per-token budget; otherwise it waits (event
    # ``("defer", rid, -1, step)`` on first deferral).
    slo: SLOConfig | None = None
    # Weight quantization for the cache-bound decode path
    # (serve/fleet/quant.py): "int8" stores per-output-channel absmax
    # int8 kernels + f32 scales and computes on their dequantization;
    # "int8_sim" is the f32-storage oracle (quantize→dequantize
    # round-trip) the real path must match bitwise. None: f32 weights.
    weight_quant: str | None = None
    # Fused decode tail (ops/decode_head.py): fold the head matmul,
    # greedy pick, and step stats into one vocab-tiled Pallas program —
    # the [slots, vocab] logits row never materializes in HBM. Dense
    # single-device layout only (capability row ``serve_fused_head_dense``
    # rejects paged / speculative / TP composition at engine init).
    # Composes with weight_quant: "int8" feeds the kernel the int8 codes
    # + scales directly, "int8_sim" runs the f32 kernel on the oracle's
    # round-tripped params.
    fused_head: bool = False

    def __post_init__(self):
        if self.slots < 1:
            raise ValueError("slots must be >= 1")
        if self.cache_kind not in KINDS:
            raise ValueError(f"cache_kind must be one of {KINDS}")
        if self.prefill_chunk < 1 or self.max_len % self.prefill_chunk:
            raise ValueError(
                f"prefill_chunk {self.prefill_chunk} must divide "
                f"max_len {self.max_len} (padded tail chunks stay in-bounds)"
            )
        if self.max_queue is not None and self.max_queue < 1:
            raise ValueError("max_queue must be >= 1 (or None for unbounded)")
        if self.deadline_s is not None and self.deadline_s <= 0:
            raise ValueError("deadline_s must be > 0 (or None)")
        if self.step_time_s is not None and self.step_time_s <= 0:
            raise ValueError("step_time_s must be > 0 (or None)")
        if self.cache_layout not in ("dense", "paged"):
            raise ValueError(
                f"cache_layout must be 'dense' or 'paged', "
                f"got {self.cache_layout!r}"
            )
        if self.cache_layout == "paged":
            if self.page_size < 1:
                raise ValueError("page_size must be >= 1")
            if self.num_pages is not None and self.num_pages < 2:
                raise ValueError(
                    "num_pages must be >= 2 (page 0 is the garbage sink)"
                )
            if self.prefix_sharing and self.page_size % self.prefill_chunk:
                raise ValueError(
                    f"prefix_sharing requires page_size "
                    f"{self.page_size} to be a multiple of prefill_chunk "
                    f"{self.prefill_chunk} (a shared head must end on a "
                    f"chunk boundary so fresh prefill never rewrites a "
                    f"shared page)"
                )
        elif self.prefix_sharing:
            raise ValueError("prefix_sharing requires cache_layout='paged'")
        if self.spec_k < 0:
            raise ValueError("spec_k must be >= 0")
        if self.weight_quant not in (None, "int8", "int8_sim"):
            raise ValueError(
                f"weight_quant must be None, 'int8' or 'int8_sim', "
                f"got {self.weight_quant!r}"
            )

    @property
    def max_pages(self) -> int:
        """Page-table width: pages covering one slot's max_len rows."""
        return math.ceil(self.max_len / self.page_size)

    @property
    def total_pages(self) -> int:
        """Pool size: ``num_pages``, defaulting to dense-equivalent
        capacity (slots × max_pages) plus the reserved garbage page."""
        if self.num_pages is not None:
            return self.num_pages
        return self.slots * self.max_pages + 1


@dataclass
class RequestStats:
    """Per-request outcome + timing ledger (all times are seconds from
    run start; latency aggregation happens in ServeReport)."""

    rid: int
    prompt_len: int
    max_new_tokens: int
    arrival: float
    staged: float | None = None  # the loop noticed the arrival (queued or rejected)
    admit_start: float | None = None  # admission began (prefill starts)
    admitted: float | None = None  # prefill finished, slot occupied
    first_token: float | None = None
    finished: float | None = None
    rejected: float | None = None  # bounced at admission control (full queue)
    expired: float | None = None  # deadline passed (queued or mid-flight)
    slot: int | None = None
    tokens: list = field(default_factory=list)
    token_times: list = field(default_factory=list)
    shared_pages: int = 0  # prefix-cache pages reused at admit (paged)
    # A model with expert layers (``route_width``): int32 blocks
    # [n, route_width], every position's expert choices in order — the
    # prompt but its last token from prefill, then one row a decode step
    # (``np.concatenate`` gives [prompt_len - 1 + len(tokens), route_width]).
    routes: list = field(default_factory=list)

    @property
    def ttft_s(self) -> float | None:
        """Time-to-first-token: arrival → first generated token."""
        if self.first_token is None:
            return None
        return self.first_token - self.arrival

    @property
    def tpot_s(self) -> float | None:
        """Mean time-per-output-token AFTER the first (decode cadence);
        None until a request has at least two tokens."""
        if len(self.token_times) < 2:
            return None
        return (self.token_times[-1] - self.token_times[0]) / (
            len(self.token_times) - 1
        )


class _InFlight(NamedTuple):
    """A decode step dispatched and not yet fetched (``ServingEngine.run``)."""

    step: int
    out: object  # its outputs, on the device: the tokens (spec: window, counts)
    rids: np.ndarray  # the request it ran in each slot, -1 where none
    owed: np.ndarray  # tokens each slot was still owed when it was dispatched
    backlog: list | None  # prefill routes launched before it (stateful)


@dataclass
class ServeReport:
    """One run's outcome: per-request stats, the scheduler event log
    (admit/evict tuples — the determinism contract), and aggregates."""

    requests: dict
    # ("admit"|"evict"|"reject"|"expire"|"defer", rid, slot, step) plus
    # ("spec", rid, slot, step, accepted_len) when spec decoding is on.
    events: list
    decode_steps: int
    wall_time: float
    peak_queue_depth: int = 0  # max waiting-line length ever observed
    busy_slot_steps: int = 0  # Σ over steps of active-slot count
    slots: int = 0  # engine slot count (occupancy denominator)
    pool_stats: dict | None = None  # paged only: prefix hits/evictions
    # The run's pass log, summed up (obs/passlog.py ``PassLog.summary``):
    # per class of pass its count, p50 / p99 / max ms, and the longest
    # passes whole with the evidence that places a stall.
    passes: dict | None = None

    @property
    def generated_tokens(self) -> int:
        return sum(len(s.tokens) for s in self.requests.values())

    @property
    def occupancy(self) -> float:
        """Mean fraction of decode-slot-steps doing useful work — the
        number a paged layout raises on mixed short/long traffic (dense
        strands capacity as queued work waits for whole max_len rows)."""
        denom = self.decode_steps * max(self.slots, 1)
        return self.busy_slot_steps / denom if denom else 0.0

    @property
    def mean_accepted_len(self) -> float:
        """Mean accepted draft tokens COMMITTED per spec step (0.0
        without spec events; windows truncated by EOS/budget count only
        what landed); tokens-per-target-step is ``1 + mean_accepted_len``
        and Σ(accepted_len + 1) equals the generated token count."""
        ls = [e[4] for e in self.events if e[0] == "spec"]
        return float(np.mean(ls)) if ls else 0.0

    def to_trace_events(self, step_time_s: float | None = None) -> list[dict]:
        """This run's event log as Chrome trace events (pure conversion —
        see :mod:`tpudml.obs.convert`); pass the run's
        ``ServeConfig.step_time_s`` for virtual-clock timestamps."""
        from tpudml.obs.convert import serve_trace_events

        return serve_trace_events(self.events, step_time_s=step_time_s)

    def annotate_ledger(self, ledger: dict[int, dict]) -> dict[int, dict]:
        """Fill the workload ledger's per-request ``ttft_s``/``tpot_s``
        fields (serve/load.py creates them as None) from this run's
        stats, in place."""
        for rid, row in ledger.items():
            st = self.requests.get(rid)
            if st is not None:
                row["ttft_s"] = st.ttft_s
                row["tpot_s"] = st.tpot_s
        return ledger

    @property
    def rejected(self) -> int:
        return sum(1 for s in self.requests.values() if s.rejected is not None)

    @property
    def expired(self) -> int:
        return sum(1 for s in self.requests.values() if s.expired is not None)

    @property
    def tokens_per_sec(self) -> float:
        return self.generated_tokens / max(self.wall_time, 1e-9)

    def latency_summary(self) -> dict:
        """p50/p99 of per-token gaps (decode cadence: consecutive token
        timestamps within a request, seeded by the admit time) and of
        end-to-end request latency (arrival → last token), plus
        time-to-first-token (arrival → first token: queueing + prefill
        + one decode step). Over every request the loop staged, the wait
        before admission in its two parts: lateness (arrival → staged:
        the loop was busy in a step and had not looked yet) and queueing
        (staged → admission start). And of the loop's steady passes (one
        decode step fetched, nobody admitted: the pass log) p50 and max."""
        gaps, e2e, ttft = [], [], []
        late = [s.staged - s.arrival for s in self.requests.values()
                if s.staged is not None]
        queued = [s.admit_start - s.staged for s in self.requests.values()
                  if s.admit_start is not None]
        for s in self.requests.values():
            if s.finished is None:
                continue
            prev = s.admitted
            for t in s.token_times:
                gaps.append(t - prev)
                prev = t
            e2e.append(s.finished - s.arrival)
            ttft.append(s.first_token - s.arrival)

        def pct(xs, q):
            return float(np.percentile(np.asarray(xs), q)) if xs else float("nan")

        def steady(key):
            ms = self.passes["classes"]["steady"][key] if self.passes else None
            return float("nan") if ms is None else ms * 1e-3

        return {
            "per_token_p50_s": pct(gaps, 50),
            "per_token_p99_s": pct(gaps, 99),
            "e2e_p50_s": pct(e2e, 50),
            "e2e_p99_s": pct(e2e, 99),
            "ttft_p50_s": pct(ttft, 50),
            "ttft_p99_s": pct(ttft, 99),
            "stage_lateness_p50_s": pct(late, 50),
            "stage_lateness_p99_s": pct(late, 99),
            "queue_wait_p50_s": pct(queued, 50),
            "queue_wait_p99_s": pct(queued, 99),
            "steady_pass_p50_s": steady("p50_ms"),
            "steady_pass_max_s": steady("max_ms"),
        }


class ServingEngine:
    """Continuous-batching prefill/decode over a ``TransformerLM`` or a
    pattern model (``HybridLM``, dense layout only).

    Single-device by default; pass ``mesh`` (+ ``axis_name``) to shard
    params, cache heads, and the decode step over a tensor-parallel axis
    (``tpudml.serve.tp`` — reuses ``tensor_parallel_rules``).
    """

    def __init__(self, model, params, config: ServeConfig | None = None,
                 *, mesh=None, axis_name: str = "model",
                 draft_model=None, draft_params=None,
                 draft_layers: int | None = None):
        self.model = model
        self.cfg = config or ServeConfig()
        cfg = self.cfg
        if (model.max_positions is not None
                and cfg.max_len > model.max_positions):
            raise ValueError(
                f"cache max_len {cfg.max_len} exceeds the position "
                f"table ({model.max_positions}); only RoPE models extrapolate"
            )
        self._paged = cfg.cache_layout == "paged"
        # A model whose per-slot state no mask hides (a recurrent state;
        # models/hybrid.py): admission zeroes the slot's state, prefill is
        # told a chunk's real length, decode is told which slots are
        # active and returns the model's counters. Its other levers are
        # not built yet: loud rejections, never a silently wrong path.
        self._stateful = bool(getattr(model, "stateful", False))
        if self._stateful:
            if self._paged:
                reject("serve_pattern_paged", exc=ServeCompositionError)
            if cfg.spec_k:
                reject("serve_pattern_spec", exc=ServeCompositionError)
            if mesh is not None:
                reject("serve_pattern_tp", exc=ServeCompositionError)
            if cfg.fused_head:
                reject("serve_pattern_fused_head", exc=ServeCompositionError)
            if cfg.weight_quant is not None:
                reject("serve_pattern_weight_quant", exc=ServeCompositionError)
        # How the decode step writes its K/V rows (``serve/dispatch``'s
        # ``row_scatter``): the page pool always scatters, the dense
        # cache where its layout allows (serve/cache.py:row_scatter).
        # How it reads them (``decode_kernel``): the dense single-token step
        # with the Pallas kernel where serve/cache.py:decode_kernel says;
        # the paged, speculative and tensor-parallel steps by einsum. A
        # model whose layers keep caches of different shapes answers for
        # all of them (``cache_forms``: models/hybrid.py).
        forms = getattr(model, "cache_forms", None)
        if forms is not None and not self._paged:
            scatter, kernel = forms(cfg.max_len, cfg.cache_kind)
        else:
            scatter = self._paged or row_scatter(model.head_dim)
            kernel = decode_kernel(cfg.cache_kind, cfg.max_len,
                                   model.num_kv_heads or model.num_heads,
                                   model.num_heads, model.head_dim)
        self._row_scatter = int(scatter)
        self._decode_kernel = int(
            not self._paged and not cfg.spec_k and mesh is None and kernel)
        if mesh is not None and (self._paged or cfg.spec_k):
            # The TP decode step shards cache heads through a shard_map
            # body that knows nothing of page tables or verify windows.
            # Until those bodies exist, composing would silently run the
            # unsharded math on sharded params — reject instead.
            reject("serve_tp_paged_spec", exc=ServeCompositionError)
        if mesh is not None and cfg.weight_quant is not None:
            # shard_params knows nothing of int8 kernels + scale trees;
            # sharding the dequantized params would silently price (and
            # store) f32 while claiming int8 — reject instead.
            reject("serve_tp_weight_quant", exc=ServeCompositionError)
        if cfg.fused_head and (mesh is not None or self._paged or cfg.spec_k):
            # The fused tail consumes the dense step's post-ln features
            # and the unsharded [d, V] head; paged/spec steps consume
            # full logits windows and TP shards the head — run those
            # unfused rather than silently falling back.
            reject("serve_fused_head_dense", exc=ServeCompositionError)
        # Weight quantization happens ONCE at init: decode compute runs
        # on the dequantized params (bitwise identical to the int8_sim
        # oracle — quant.py's contract), while the "int8" mode keeps the
        # int8 kernels + scales as the params of record so storage
        # accounting (quantized_param_bytes) reflects what a chip would
        # actually hold resident.
        self.quantized_params = None
        self.quant_scales = None
        if cfg.weight_quant is not None:
            from tpudml.serve.fleet.quant import (
                dequantize_params,
                quantize_params,
                sim_quantize_params,
            )

            if cfg.weight_quant == "int8":
                self.quantized_params, self.quant_scales = quantize_params(
                    params
                )
                params = dequantize_params(
                    self.quantized_params, self.quant_scales
                )
            else:  # int8_sim: the f32-storage oracle
                params = sim_quantize_params(params)
        self._tp = None
        if mesh is not None:
            from tpudml.serve.tp import TPServing

            self._tp = TPServing(model, mesh, axis_name, self.cfg)
            self.params = self._tp.shard_params(params)
            self.caches = self._tp.init_caches()
            self._decode = self._tp.decode_step
            self._prefill_cache = self._tp._prefill_cache
            self._prefill_builder = self._tp.prefill_at
        else:
            self.params = params
            if self._paged:
                self.caches = model.init_paged_cache(
                    cfg.total_pages, cfg.page_size, cfg.cache_kind
                )
                self._decode = make_paged_decode_step(model)
                self._prefill_builder = self._build_prefill_paged
            else:
                self.caches = model.init_decode_cache(
                    cfg.slots, cfg.max_len, cfg.cache_kind
                )
                if cfg.fused_head:
                    hq = hs = None
                    if self.quantized_params is not None:
                        hq = self.quantized_params["head"]["kernel"]
                        hs = self.quant_scales["head"]["kernel"]
                    self._decode = make_fused_decode_step(
                        model, head_q=hq, head_scale=hs
                    )
                elif self._stateful:
                    self._decode = make_stateful_decode_step(model)
                    self._reset_slot = jax.jit(model.reset_slot,
                                               donate_argnums=(0,))
                    self._route_backlog: list = []
                    # Allocated once; ``serve/dispatch`` carries them.
                    self._cache_bytes = model.cache_bytes(self.caches)
                else:
                    self._decode = make_decode_step(model)
                self._prefill_builder = self._build_prefill
            self._prefill_cache = {}
        # Paged bookkeeping: the host-side allocator plus the
        # [slots, max_pages] table the decode step reads through.
        self._pool = None
        self._table = None
        self._slot_pages: list[list[int]] = [[] for _ in range(cfg.slots)]
        if self._paged:
            self._pool = PagePool(
                cfg.total_pages, cfg.page_size, cfg.prefix_sharing
            )
            self._table = np.zeros((cfg.slots, cfg.max_pages), np.int32)
        # Speculative decoding: default draft is the target's lower
        # trunk (zero extra weights); exactness never depends on it.
        self._spec = None
        self.draft_model = None
        if cfg.spec_k:
            if draft_model is None:
                n = draft_layers or max(1, model.num_layers // 2)
                draft_model, draft_params = draft_from_trunk(model, params, n)
            elif draft_params is None:
                raise ValueError("draft_model requires draft_params")
            self.draft_model = draft_model
            self._dparams = draft_params
            # The draft cache stays dense in every mode — it is small by
            # construction and only ever single-token-stepped.
            self._dcaches = draft_model.init_decode_cache(
                cfg.slots, cfg.max_len, cfg.cache_kind
            )
            self._dprefill_cache = {}
            self._spec = make_spec_decode_step(
                model, draft_model, cfg.spec_k, paged=self._paged
            )
        # How many decode steps stay in flight while the host fetches and
        # commits the one before (``run``): one where the next step's
        # inputs are the host's own arithmetic (a plain step advances each
        # active slot by one token, and that token can stay on the device);
        # none where they are data the host must see first (a speculative
        # step advances a slot by its accepted length) or bookkeeping the
        # commit does (the page pool).
        self._lookahead = int(self._spec is None and not self._paged)
        if self._lookahead:
            self._step = _with_device_tokens(self._decode, cfg.slots,
                                             self._stateful)
            width = cfg.slots
            if self._stateful:  # counters and routes ride behind the tokens
                width += len(model.counter_names) + cfg.slots * model.route_width
            self._no_prev = jnp.zeros(width, jnp.int32)
        # SLO admission pricing (deterministic, host-side).
        self._cost = None
        if cfg.slo is not None:
            self._cost = DecodeCostModel(
                model, cfg, cfg.slo,
                world=self._tp.world if self._tp is not None else 1,
                draft_model=self.draft_model,
            )

    # ------------------------------------------------------------ prefill

    def _build_prefill(self, start: int):
        model = self.model

        if self._stateful:
            def _serve_prefill_chunk(params, caches, chunk, slot, n_real):
                return model.apply_prefill(params, caches, chunk, slot, start,
                                           n_real)
        else:
            def _serve_prefill_chunk(params, caches, chunk, slot):
                return model.apply_prefill(params, caches, chunk, slot, start)

        return jax.jit(_serve_prefill_chunk, donate_argnums=(1,))

    def _build_prefill_paged(self, start: int):
        model = self.model

        def _serve_prefill_chunk(params, caches, chunk, table_row):
            return model.apply_prefill_paged(params, caches, table_row,
                                             chunk, start)

        return jax.jit(_serve_prefill_chunk, donate_argnums=(1,))

    def _prefill_at(self, start: int):
        fn = self._prefill_cache.get(start)
        if fn is None:
            fn = self._prefill_cache[start] = self._prefill_builder(start)
        return fn

    def _build_prefill_draft(self, start: int):
        draft = self.draft_model

        def _serve_prefill_chunk(dparams, dcaches, chunk, slot):
            return draft.apply_prefill(dparams, dcaches, chunk, slot, start)

        return jax.jit(_serve_prefill_chunk, donate_argnums=(1,))

    def _prefill_draft(self, slot: int, prompt: np.ndarray) -> None:
        """Spec only: the DRAFT cache needs the prompt too — a draft
        proposing from an unprefilled history is pure noise, zeroing
        acceptance (exactness never cared, throughput very much did).
        It is per-slot dense and never shares prefix pages, so the whole
        head is prefilled even when the target's pages were shared."""
        if self._spec is None:
            return
        p = prompt.size - 1
        c = self.cfg.prefill_chunk
        slot_j = jnp.asarray(slot, jnp.int32)
        for s0 in range(0, p, c):
            chunk = np.zeros((1, c), np.int32)
            n = min(c, p - s0)
            chunk[0, :n] = prompt[s0:s0 + n]
            fn = self._dprefill_cache.get(s0)
            if fn is None:
                fn = self._dprefill_cache[s0] = self._build_prefill_draft(s0)
            self._dcaches = fn(
                self._dparams, self._dcaches, jnp.asarray(chunk), slot_j
            )

    def _spec_headroom(self) -> int:
        return self.cfg.spec_k if self._spec is not None else 0

    def _validate_request(self, req: Request) -> np.ndarray:
        prompt = np.asarray(req.prompt, np.int32)
        if prompt.ndim != 1 or prompt.size < 1:
            raise ValueError(f"request {req.rid}: prompt must be [L>=1]")
        total = prompt.size + req.max_new_tokens + self._spec_headroom()
        if total > self.cfg.max_len:
            extra = (
                f" (+ spec_k {self.cfg.spec_k} verify headroom)"
                if self._spec_headroom() else ""
            )
            raise ValueError(
                f"request {req.rid}: prompt {prompt.size} + "
                f"max_new_tokens {req.max_new_tokens}{extra} exceeds "
                f"cache max_len {self.cfg.max_len}"
            )
        return prompt

    def _admit(self, slot: int, req: Request) -> tuple[int, int]:
        """Prefill ``req``'s prompt (all but the last token) into a
        slot's cache rows; returns (pos, last_token) for the decode
        state. Chunk tails are padded — padded rows land at positions
        the mask excludes until decode overwrites them. A stateful
        model's slot state is zeroed first (a one-token prompt runs no
        chunk at all and would inherit the last tenant's state), and its
        prefill is told how many tokens of each chunk are real."""
        prompt = self._validate_request(req)
        p = prompt.size - 1
        c = self.cfg.prefill_chunk
        starts = range(0, p, c)
        with self._admit_span(req, slot, prompt, len(starts), 0):
            slot_j = jnp.asarray(slot, jnp.int32)
            if self._stateful:
                self.caches = self._reset_slot(self.caches, slot_j)
            for s0 in starts:
                chunk = np.zeros((1, c), np.int32)
                n = min(c, p - s0)
                chunk[0, :n] = prompt[s0:s0 + n]
                if self._stateful:
                    # The chunk's routes stay on the device until the
                    # fetch of the next decode step dispatched
                    # (``_collect_routes``).
                    self.caches, routes = self._prefill_at(s0)(
                        self.params, self.caches, jnp.asarray(chunk), slot_j,
                        np.int32(n))
                    if routes.shape[1]:
                        self._route_backlog.append((req.rid, routes, n))
                else:
                    self.caches = self._prefill_at(s0)(
                        self.params, self.caches, jnp.asarray(chunk), slot_j
                    )
            self._prefill_draft(slot, prompt)
        return p, int(prompt[-1])

    @staticmethod
    def _collect_routes(stats: dict, backlog: list) -> None:
        """Move prefill chunks' routes to their requests. ``backlog`` is
        what admission had launched when a decode step was dispatched, and
        this is called behind that step's fetch: the chunks ran before the
        step, so each is a plain copy, not a wait."""
        for rid, routes, n in backlog:
            stats[rid].routes.append(np.asarray(routes)[:n])

    def _admit_span(self, req: Request, slot: int, prompt: np.ndarray,
                    chunks: int, shared_pages: int):
        """``serve/admit``: the host side of one request's prefill, opened
        once admission is certain. ``chunks`` counts the prefill programs
        it launches (the draft's too under speculative decoding), and
        ``trunk_prefilled`` the layers each of them runs: all of them,
        unless the model's last layers keep nothing of a prompt token
        (``prefill_entries``: models/hybrid.py)."""
        if self._spec is not None:
            chunks += len(range(0, prompt.size - 1, self.cfg.prefill_chunk))
        return span("admit", "serve", rid=req.rid, slot=slot,
                    prompt_len=int(prompt.size), chunks=chunks,
                    shared_pages=shared_pages,
                    state_reset=int(self._stateful),
                    trunk_prefilled=getattr(self.model, "prefill_entries",
                                            self.model.num_layers))

    def _admit_paged(self, slot: int, req: Request,
                     stats: RequestStats) -> tuple[int, int] | None:
        """Paged admission: map pages into the slot's table row — prefix
        hits first (refcounted, skipping their prefill entirely), fresh
        pages for the rest — then prefill from the first unshared
        position. Returns None (leaving the pool untouched and the
        request queued) when the pool cannot supply the fresh pages; the
        caller defers FIFO-preservingly."""
        cfg = self.cfg
        prompt = self._validate_request(req)
        total = prompt.size + req.max_new_tokens + self._spec_headroom()
        p = prompt.size - 1
        pool = self._pool
        needed = math.ceil(total / cfg.page_size)
        shared = pool.match_prefix(prompt)  # only pages ending before p
        # Acquire the matched pages BEFORE allocating fresh ones: taking
        # a reference pulls a retained page out of the eviction LRU, so
        # a pressured alloc_n can never evict a page we are about to map
        # as this slot's prefix (which would alias the same pool page at
        # two table rows and let decode writes corrupt the prompt K/V).
        for pid in shared:
            pool.acquire(pid)
        fresh = pool.alloc_n(needed - len(shared))
        if fresh is None:
            for pid in shared:
                pool.release(pid)
            return None
        if shared:
            pool.prefix_hits += 1
            pool.pages_reused += len(shared)
        # Prefill [n_shared·P, p) — a chunk-aligned start by the
        # page_size % prefill_chunk == 0 config rule, so a fresh chunk
        # never writes into a shared page.
        c = cfg.prefill_chunk
        starts = range(len(shared) * cfg.page_size, p, c)
        with self._admit_span(req, slot, prompt, len(starts), len(shared)):
            pages = shared + fresh
            row = np.zeros(cfg.max_pages, np.int32)
            row[: len(pages)] = pages
            self._table[slot] = row
            self._slot_pages[slot] = pages
            stats.shared_pages = len(shared)
            row_j = jnp.asarray(row)
            for s0 in starts:
                chunk = np.zeros((1, c), np.int32)
                n = min(c, p - s0)
                chunk[0, :n] = prompt[s0:s0 + n]
                self.caches = self._prefill_at(s0)(
                    self.params, self.caches, jnp.asarray(chunk), row_j
                )
            if pool.prefix_sharing:
                # Publish this request's fully-prefilled fresh pages: page
                # j is shareable iff it ends strictly before the first
                # decode write at p, so no future occupant ever writes it.
                for j in range(len(shared), len(pages)):
                    if (j + 1) * cfg.page_size <= p:
                        pool.register(pages[j], prompt, j)
            self._prefill_draft(slot, prompt)
        return p, int(prompt[-1])

    def _release_slot(self, slot: int) -> None:
        """Return a finished/expired slot's pages to the allocator and
        zero its table row (pointing future don't-care writes at the
        garbage page)."""
        if self._pool is None:
            return
        for pid in self._slot_pages[slot]:
            self._pool.release(pid)
        self._slot_pages[slot] = []
        self._table[slot] = 0

    # ---------------------------------------------------------------- run

    def run(self, requests: list[Request]) -> ServeReport:
        """Serve a request stream to completion. Arrival times are
        honored open-loop (a request only becomes admissible once the
        clock passes its arrival), decode advances every occupied slot
        one token per step, finished slots are refilled mid-flight from
        the waiting queue. Every request ends in EXACTLY ONE terminal
        state: finished, rejected (bounded queue full at arrival), or
        expired (deadline passed while queued or in flight) — the
        ledger-accounting invariant the overload tests audit.

        A pass stages, admits, dispatches step N + 1 and only then fetches
        and commits step N (``self._lookahead`` 1: the device always has
        its next program queued, and the host's work runs under a running
        step). The slot state below (``pos``, ``remaining``, ``active``) is
        therefore kept as of the last DISPATCH: a plain step advances each
        active slot by one token, so an answer that ends by count is known
        to end when its last step is dispatched, its slot takes a new
        tenant in the next pass (whose prefill chunks queue on the device
        behind that step), and the schedule in steps is the one of a loop
        that fetched before it dispatched. An ending only the token or the
        clock tells (``eos_token``, ``deadline_s`` mid-flight) is seen at
        commit N with N + 1 already in flight: that slot's token of N + 1
        is dropped, and the slot frees one step later. With a lookahead of
        0 (speculative, paged) a pass fetches and commits the step it
        dispatched.

        Every run keeps a pass log (``tpudml.obs.passlog``, fed by the
        loop's spans): its summary is ``ServeReport.passes``.
        """
        with pass_log("serve") as passes:
            report = self._serve(requests, passes)
        report.passes = passes.summary()
        return report

    def _serve(self, requests: list[Request], passes) -> ServeReport:
        cfg = self.cfg
        b = cfg.slots
        arrivals = deque(sorted(requests, key=lambda r: (r.arrival_time, r.rid)))
        queue: deque[Request] = deque()  # arrived, not yet admitted
        stats = {
            r.rid: RequestStats(
                rid=r.rid, prompt_len=len(r.prompt),
                max_new_tokens=r.max_new_tokens, arrival=r.arrival_time,
            )
            for r in requests
        }
        if len(stats) != len(requests):
            raise ValueError("duplicate request ids")

        last = np.zeros(b, np.int32)
        pos = np.zeros(b, np.int32)
        remaining = np.zeros(b, np.int64)
        slot_rid = np.full(b, -1, np.int64)
        active = np.zeros(b, bool)
        deadline_s = np.inf if cfg.deadline_s is None else cfg.deadline_s
        in_flight: deque[_InFlight] = deque()  # oldest first
        events: list = []
        steps = 0  # decode steps dispatched
        peak_queue = 0
        busy_slot_steps = 0
        deferred_logged: set[int] = set()  # one "defer" event per rid
        # Clock: wall time by default; virtual (decode-step-derived) when
        # cfg.step_time_s is set — see ServeConfig. ``now(n)`` is the clock
        # behind ``n`` steps, for a commit that runs with one more in flight.
        t0 = time.perf_counter()
        v_extra = 0.0  # virtual-clock idle skips (accumulated)
        if cfg.step_time_s is not None:
            now = lambda n=None: (  # noqa: E731
                (steps if n is None else n) * cfg.step_time_s + v_extra)
        else:
            now = lambda n=None: time.perf_counter() - t0  # noqa: E731
        passes.clock = now  # a pass's start, on the clock of RequestStats

        while arrivals or queue or active.any() or in_flight:
            t = now()
            # The events of a step already in flight go in ahead of this
            # pass's own (staging, admission): the log reads in the order
            # of the steps, an eviction before the refill of its slot.
            mark = len(events) if in_flight else None
            with span("iter", "serve", step=steps,
                      active=int(active.sum())) as this_pass:
                # Stage arrivals into the waiting queue; a full bounded
                # queue rejects at the door (slot -1 in the event tuple).
                while arrivals and arrivals[0].arrival_time <= t:
                    req = arrivals.popleft()
                    rejected = (cfg.max_queue is not None
                                and len(queue) >= cfg.max_queue)
                    with span("arrive", "serve", rid=req.rid,
                              late_us=int((t - req.arrival_time) * 1e6),
                              rejected=int(rejected)):
                        stats[req.rid].staged = t
                        if rejected:
                            stats[req.rid].rejected = t
                            events.append(("reject", req.rid, -1, steps))
                        else:
                            queue.append(req)
                this_pass.set_metadata(queue=len(queue))  # depth after staging
                peak_queue = max(peak_queue, len(queue))
                # Expire queued requests strictly past arrival + deadline
                # BEFORE admission — never spend prefill on a dead request.
                if cfg.deadline_s is not None:
                    kept: deque[Request] = deque()
                    while queue:
                        req = queue.popleft()
                        if t > req.arrival_time + cfg.deadline_s:
                            stats[req.rid].expired = t
                            events.append(("expire", req.rid, -1, steps))
                        else:
                            kept.append(req)
                    queue = kept
                # Admit: free slots in index order, queue in arrival order.
                # The head is only ever PEEKED until admission succeeds —
                # an SLO deferral or a page-starved pool leaves it queued,
                # and nothing behind it may overtake (FIFO + (arrival, rid)
                # order is the determinism contract).
                for i in range(b):
                    if active[i] or not queue:
                        continue
                    req = queue[0]
                    if self._cost is not None and not self._cost.admit_ok(
                        int(active.sum())
                    ):
                        if req.rid not in deferred_logged:
                            deferred_logged.add(req.rid)
                            events.append(("defer", req.rid, -1, steps))
                        break
                    st = stats[req.rid]
                    st.admit_start = now()
                    if self._paged:
                        admitted = self._admit_paged(i, req, st)
                        if admitted is None:
                            if not active.any():
                                raise ValueError(
                                    f"request {req.rid} needs more pages "
                                    f"than the pool can ever supply "
                                    f"({cfg.total_pages} pages incl. the "
                                    f"garbage page)"
                                )
                            if req.rid not in deferred_logged:
                                deferred_logged.add(req.rid)
                                events.append(("defer", req.rid, -1, steps))
                            break
                    else:
                        admitted = self._admit(i, req)
                    queue.popleft()
                    pos[i], last[i] = admitted
                    remaining[i] = req.max_new_tokens
                    slot_rid[i] = req.rid
                    active[i] = True
                    st.admitted = now()
                    st.slot = i
                    events.append(("admit", req.rid, i, steps))
                n_active = int(active.sum())
                if not n_active and not in_flight:
                    if not arrivals:
                        continue  # queue drained by expiry; loop re-checks
                    # Idle: nothing in flight, queue head hasn't arrived yet.
                    gap = arrivals[0].arrival_time - now()
                    if cfg.step_time_s is not None:
                        v_extra += max(gap, 0.0)  # skip virtual time forward
                    elif gap > 0:
                        with span("idle", "serve"):
                            time.sleep(min(gap, 0.05))
                    continue
                if n_active:
                    # One decode step for ALL slots. Inactive slots run garbage
                    # tokens at stale positions — harmless by the mask argument
                    # in the module docstring (paged: their zero table rows
                    # point every write at the garbage page) — so the compiled
                    # shape never changes with occupancy. Spec steps return a
                    # K+1-wide window + per-slot commit counts; plain steps
                    # reduce to the same contract at width 1.
                    busy_slot_steps += n_active
                    # ``ahead``: 1 when the step before is still unfetched
                    # (this one takes that one's tokens on the device).
                    # ``rows``: cache rows that hold a token, of the
                    # slots x max_len the dense step reads.
                    # ``state_slots``: slots whose recurrent state the step
                    # reads and writes back (a stateful model's active slots).
                    # A stateful model adds its caches' own counters: live
                    # rows by layer kind (``rows_full``, ``rows_window``;
                    # ``rows_read_full``: times the layers that read them),
                    # the recurrent ``state_bytes`` of the active slots, and
                    # the bytes allocated to each kind (``cache_bytes_*``).
                    with span("dispatch", "serve", step=steps, active=n_active,
                              ahead=int(bool(in_flight)),
                              rows=int(pos[active].sum()),
                              row_scatter=self._row_scatter,
                              decode_kernel=self._decode_kernel,
                              state_slots=n_active if self._stateful else 0,
                              **({**self.model.live_rows(pos[active], cfg.max_len),
                                  **self._cache_bytes}
                                 if self._stateful else {})):
                        rids = np.where(active, slot_rid, -1)
                        backlog = None
                        if self._lookahead:
                            # A slot is fresh unless the step before ran it
                            # for the same request: then its token is there.
                            prev, fresh = self._no_prev, np.ones(b, bool)
                            if in_flight:
                                prev = in_flight[-1].out
                                fresh = rids != in_flight[-1].rids
                            if self._stateful:
                                backlog, self._route_backlog = self._route_backlog, []
                            out, _, self.caches = self._step(
                                self.params, self.caches, prev,
                                np.array([last, pos, active, fresh], np.int32))
                        else:
                            # Copies of its own: the CPU backend may read a
                            # NumPy buffer late, and ``pos`` moves on below.
                            state = (last.copy(), pos.copy())
                            table = (jnp.asarray(self._table),) if self._paged else ()
                            if self._spec is not None:
                                emitted, n_emit, _, self.caches, self._dcaches = (
                                    self._spec(self.params, self._dparams,
                                               self.caches, self._dcaches,
                                               *table, *state))
                                out = (emitted, n_emit)
                            else:
                                out, _, self.caches = self._decode(
                                    self.params, self.caches, *table, *state)
                    in_flight.append(_InFlight(steps, out, rids, remaining.copy(),
                                               backlog))
                    steps += 1
                    # A step emits a token a slot (a speculative one at
                    # least; its commit adds the rest), so who is still
                    # active after it is known now.
                    pos[active] += 1
                    remaining[active] -= 1
                    active &= remaining > 0
                while len(in_flight) > (self._lookahead if n_active else 0):
                    step, out, rids, owed, backlog = in_flight.popleft()
                    counters = routes_np = None
                    # Where the host waits for the device.
                    with span("fetch", "serve", step=step):
                        if self._spec is not None:
                            emitted_np = np.asarray(jax.device_get(out[0]))
                            n_emit_np = np.asarray(jax.device_get(out[1]))
                        else:
                            next_np = np.asarray(jax.device_get(out))
                            if self._stateful:  # counters, then routes, ride behind the tokens
                                names = self.model.counter_names
                                counters = dict(zip(
                                    names, next_np[b:b + len(names)].tolist()))
                                routes_np = next_np[b + len(names):].reshape(b, -1)
                                if backlog:
                                    self._collect_routes(stats, backlog)
                            emitted_np = next_np[:b, None]
                            n_emit_np = np.ones(b, np.int64)
                    t_step = now(step + 1)
                    logged: list = []
                    with span("commit", "serve", step=step) as commit:
                        n_tokens = n_finished = n_expired = 0
                        for i in range(b):
                            if rids[i] < 0:
                                continue
                            st = stats[rids[i]]
                            if st.finished is not None or st.expired is not None:
                                # Ended at the commit before this one, which
                                # this step was already in flight behind:
                                # its token for the slot is dropped.
                                continue
                            if routes_np is not None and routes_np.shape[1]:
                                st.routes.append(routes_np[i:i + 1])
                            done = False
                            committed = 0
                            for tok in emitted_np[i, : int(n_emit_np[i])]:
                                tok = int(tok)
                                st.tokens.append(tok)
                                st.token_times.append(t_step)
                                committed += 1
                                if st.first_token is None:
                                    st.first_token = t_step
                                if committed >= owed[i] or (
                                    cfg.eos_token is not None
                                    and tok == cfg.eos_token
                                ):
                                    done = True
                                    break
                            n_tokens += committed
                            # The slot's state is this request's to move
                            # until admission hands the slot on (it may
                            # have: an ending by count frees it a pass early).
                            mine = slot_rid[i] == rids[i]
                            if mine:
                                last[i] = tok
                                pos[i] += committed - 1
                                remaining[i] -= committed - 1
                            if self._spec is not None:
                                # accepted_len counts draft tokens actually
                                # COMMITTED (committed - 1: the last commit is
                                # the target's bonus/correction token) — a
                                # window truncated by EOS or the max_new_tokens
                                # budget logs only what landed in the ledger,
                                # so mean_accepted_len stays an exact
                                # tokens-per-target-step accounting.
                                logged.append(("spec", int(rids[i]), i, step + 1,
                                               committed - 1))
                            if done:
                                st.finished = t_step
                                logged.append(("evict", int(rids[i]), i, step + 1))
                                n_finished += 1
                            elif t_step > st.arrival + deadline_s:
                                # Mid-flight deadline eviction at the step
                                # boundary: the slot frees for the queue head,
                                # the partial tokens stay in the ledger,
                                # finished stays None.
                                st.expired = t_step
                                logged.append(("expire", int(rids[i]), i, step + 1))
                                n_expired += 1
                            else:
                                continue
                            self._release_slot(i)
                            if mine:
                                active[i] = False
                                slot_rid[i] = -1
                        commit.set_metadata(tokens=n_tokens, finished=n_finished,
                                            expired=n_expired, **(counters or {}))
                    if mark is None:
                        events.extend(logged)
                    else:
                        events[mark:mark] = logged
                        mark += len(logged)
        pool_stats = None
        if self._pool is not None:
            pool_stats = {
                "prefix_hits": self._pool.prefix_hits,
                "pages_reused": self._pool.pages_reused,
                "retained_evictions": self._pool.retained_evictions,
            }
        return ServeReport(
            requests=stats, events=events, decode_steps=steps,
            wall_time=now(), peak_queue_depth=peak_queue,
            busy_slot_steps=busy_slot_steps, slots=b, pool_stats=pool_stats,
        )
