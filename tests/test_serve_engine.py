"""Continuous-batching scheduler contract.

Load-bearing properties: a fixed workload seed reproduces the exact
eviction/refill event sequence and token streams (determinism), every
queued request completes with exactly the token count the load
generator's ledger owes it (accounting, no starvation), slots are
actually reused mid-flight (continuous batching, not drain-and-refill),
and the config validators reject the shapes that would silently corrupt
a cache.
"""

import math

import jax
import numpy as np
import pytest

from tpudml.models import TransformerLM
from tpudml.serve import (
    Request,
    ServeConfig,
    ServingEngine,
    poisson_workload,
)

V = 48


def _model():
    return TransformerLM(vocab_size=V, embed_dim=32, num_heads=4,
                         num_layers=2, max_len=64, rope=True,
                         num_kv_heads=2)


@pytest.fixture(scope="module")
def setup():
    model = _model()
    params, _ = model.init(jax.random.key(0))
    return model, params


def _run(model, params, n=10, seed=11, slots=3, **wl):
    cfg = ServeConfig(slots=slots, max_len=64, prefill_chunk=8)
    eng = ServingEngine(model, params, cfg)
    base = dict(vocab_size=V, prompt_len=(2, 12), new_tokens=(3, 8))
    base.update(wl)
    reqs, ledger = poisson_workload(n, math.inf, seed, **base)
    return eng.run(reqs), ledger


def test_every_request_completes_with_owed_tokens(setup):
    """No starvation, exact accounting: 10 requests through 3 slots all
    finish with precisely ledger[rid]['max_new_tokens'] tokens."""
    rep, ledger = _run(*setup)
    assert set(rep.requests) == set(ledger)
    for rid, owed in ledger.items():
        st = rep.requests[rid]
        assert st.finished is not None, f"request {rid} starved"
        assert len(st.tokens) == owed["max_new_tokens"]
        assert len(st.token_times) == len(st.tokens)
        assert st.prompt_len == owed["prompt_len"]
        assert st.admitted is not None and st.first_token is not None
        assert st.arrival <= st.admitted <= st.first_token <= st.finished
    assert rep.generated_tokens == sum(
        o["max_new_tokens"] for o in ledger.values())


def test_event_log_is_deterministic(setup):
    model, params = setup
    rep1, _ = _run(model, params)
    rep2, _ = _run(model, params)
    assert rep1.events == rep2.events
    assert rep1.decode_steps == rep2.decode_steps
    for rid in rep1.requests:
        assert rep1.requests[rid].tokens == rep2.requests[rid].tokens
        assert rep1.requests[rid].slot == rep2.requests[rid].slot


@pytest.mark.parametrize("mode", ["f32", "int8", "spec", "tp"])
def test_row_scatter_serves_the_per_slot_update_tokens(mode, monkeypatch):
    """At head_dim 128 the decode step writes its K/V rows by one scatter
    a tensor (serve/cache.py:row_scatter). Ten requests through three
    slots — released and re-admitted over stale rows — are served the
    tokens, in the slots and at the steps, of the per-slot
    ``dynamic_update_slice`` form: plain decode, an int8 cache (codes and
    scales), the speculative verify window (Q = 3 rows a slot) and the
    head-sharded TP step."""
    from tpudml.serve import cache

    model = TransformerLM(vocab_size=V, embed_dim=256, num_heads=2,
                          num_layers=2, max_len=64, rope=True,
                          num_kv_heads=2 if mode == "tp" else 1)
    params, _ = model.init(jax.random.key(1))
    cfg = ServeConfig(slots=3, max_len=64, prefill_chunk=8,
                      cache_kind="int8" if mode == "int8" else "f32",
                      spec_k=2 if mode == "spec" else 0)
    extra = {}
    if mode == "tp":
        from tpudml.core.config import MeshConfig
        from tpudml.core.dist import make_mesh

        extra = dict(mesh=make_mesh(MeshConfig({"model": 2}),
                                    jax.devices()[:2]), axis_name="model")
    reqs, _ = poisson_workload(10, math.inf, 11, vocab_size=V,
                               prompt_len=(2, 12), new_tokens=(3, 8))

    def serve():
        return ServingEngine(model, params, cfg, **extra).run(reqs)

    assert cache.row_scatter(256 // 2)
    got = serve()
    monkeypatch.setattr(cache, "row_scatter", lambda head_dim: False)
    want = serve()
    assert got.events == want.events
    assert sum(e[0] == "evict" for e in got.events) == 10
    for rid, st in want.requests.items():
        assert got.requests[rid].tokens == st.tokens


def _mqa_128():
    return TransformerLM(vocab_size=V, embed_dim=256, num_heads=2,
                         num_layers=2, max_len=64, rope=True, num_kv_heads=1)


def _pattern_128():
    from tpudml.models import HybridLM

    return HybridLM(vocab_size=V, pattern="M*E*", embed_dim=64, num_heads=4,
                    num_kv_heads=2, head_dim=128, mamba_heads=4,
                    mamba_head_dim=16, n_groups=2, state_size=16,
                    chunk_size=8, num_experts=8, top_k=2, expert_dim=24,
                    shared_dim=40)


@pytest.mark.parametrize("build", [_mqa_128, _pattern_128],
                         ids=["transformer_mqa", "pattern_gqa"])
def test_decode_kernel_serves_the_einsum_tokens(build, monkeypatch):
    """Where query heads share a K/V head at head_dim 128 the decode step
    reads the cache with the Pallas kernel (serve/cache.py:decode_kernel;
    interpreted here). Ten requests through three slots — released and
    re-admitted over stale rows — are served the einsum path's tokens, in
    its slots and at its steps, and ``serve/dispatch`` says which read
    the step made."""
    from tpudml.obs import Tracer, use_tracer
    from tpudml.ops import decode_attn

    model = build()
    params, _ = model.init(jax.random.key(1))
    cfg = ServeConfig(slots=3, max_len=64, prefill_chunk=8)
    reqs, _ = poisson_workload(10, math.inf, 11, vocab_size=V,
                               prompt_len=(2, 12), new_tokens=(3, 8))

    def serve():
        tracer = Tracer()
        with use_tracer(tracer):
            rep = ServingEngine(model, params, cfg).run(reqs)
        flags = {s.args["decode_kernel"] for s in tracer.events
                 if (s.cat, s.name) == ("serve", "dispatch")}
        return rep, flags

    want, flags = serve()
    assert flags == {0}
    calls = []
    real = decode_attn.decode_attn
    monkeypatch.setattr(decode_attn, "kernel_interpret", lambda: True)
    monkeypatch.setattr(decode_attn, "decode_attn",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    got, flags = serve()
    assert flags == {1} and len(calls) == 2  # traced once, two layers
    assert got.events == want.events
    assert sum(e[0] == "evict" for e in got.events) == 10
    for rid, st in want.requests.items():
        assert got.requests[rid].tokens == st.tokens


@pytest.mark.parametrize("kw", [dict(cache_layout="paged", page_size=8),
                                dict(spec_k=2), dict(cache_kind="int8")],
                         ids=["paged", "spec", "int8"])
def test_decode_kernel_flag_is_down_where_the_step_einsums(kw, monkeypatch):
    """The paged and speculative steps and the int8 cache keep their
    einsums on a TPU too, and ``serve/dispatch`` says so."""
    from tpudml.ops import decode_attn

    monkeypatch.setattr(decode_attn, "kernel_interpret", lambda: True)
    model = _mqa_128()
    params, _ = model.init(jax.random.key(1))
    plain = ServingEngine(model, params, ServeConfig(
        slots=3, max_len=64, prefill_chunk=8))
    other = ServingEngine(model, params, ServeConfig(
        slots=3, max_len=64, prefill_chunk=8, **kw))
    assert (plain._decode_kernel, other._decode_kernel) == (1, 0)


def test_slots_are_refilled_mid_flight(setup):
    """Continuous batching: with more requests than slots, some admit
    happens at a decode step > 0 (a freed slot re-enters the batch while
    other slots are mid-generation), every admit/evict pairs up, and a
    slot never holds two live requests."""
    rep, _ = _run(*setup)
    admits = [e for e in rep.events if e[0] == "admit"]
    assert any(e[3] > 0 for e in admits), "no mid-flight refill happened"
    live = {}
    for kind, rid, slot, _step in rep.events:
        if kind == "admit":
            assert slot not in live, f"slot {slot} double-occupied"
            live[slot] = rid
        else:
            assert live.pop(slot) == rid
    assert not live


def test_fifo_admission_order(setup):
    """With all arrivals at t=0, admission order is request id order
    (FIFO with rid tie-break) — the queue head is never bypassed."""
    rep, _ = _run(*setup)
    admitted = [e[1] for e in rep.events if e[0] == "admit"]
    assert admitted == sorted(admitted)


def test_eos_token_stops_early(setup):
    """Re-running with eos_token set to a token the greedy stream is
    known (from a reference run) to emit cuts that request short."""
    model, params = setup
    ref, _ = _run(model, params, n=4, seed=5)
    rid, st = next((r, s) for r, s in ref.requests.items()
                   if len(s.tokens) >= 2)
    eos = st.tokens[0]
    cfg = ServeConfig(slots=3, max_len=64, prefill_chunk=8, eos_token=eos)
    eng = ServingEngine(model, params, cfg)
    reqs, _ = poisson_workload(4, math.inf, 5, vocab_size=V,
                               prompt_len=(2, 12), new_tokens=(3, 8))
    rep = eng.run(reqs)
    st2 = rep.requests[rid]
    assert len(st2.tokens) == 1 and st2.tokens[0] == eos
    for s in rep.requests.values():  # every stream stops at eos or budget
        assert s.tokens[-1] == eos or len(s.tokens) == len(
            ref.requests[s.rid].tokens)


def test_latency_summary_and_throughput(setup):
    rep, _ = _run(*setup, n=5)
    lat = rep.latency_summary()
    for key in ("per_token_p50_s", "per_token_p99_s", "e2e_p50_s",
                "e2e_p99_s", "ttft_p50_s", "ttft_p99_s"):
        assert np.isfinite(lat[key]) and lat[key] >= 0
    assert lat["per_token_p50_s"] <= lat["per_token_p99_s"]
    assert rep.tokens_per_sec > 0
    assert rep.wall_time > 0


def test_oversized_request_rejected(setup):
    model, params = setup
    eng = ServingEngine(model, params,
                        ServeConfig(slots=1, max_len=64, prefill_chunk=8))
    big = Request(rid=0, prompt=np.zeros(60, np.int32), max_new_tokens=10)
    with pytest.raises(ValueError, match="exceeds cache max_len"):
        eng.run([big])


def test_config_validation():
    with pytest.raises(ValueError, match="divide"):
        ServeConfig(slots=2, max_len=64, prefill_chunk=7)
    with pytest.raises(ValueError, match="cache_kind"):
        ServeConfig(cache_kind="fp4")
    with pytest.raises(ValueError, match="slots"):
        ServeConfig(slots=0)


def test_workload_generator_contract():
    reqs, ledger = poisson_workload(6, 2.0, 3, vocab_size=V,
                                    prompt_len=(1, 4), new_tokens=(2, 5))
    arrivals = [r.arrival_time for r in reqs]
    assert arrivals == sorted(arrivals) and arrivals[0] > 0
    reqs2, _ = poisson_workload(6, 2.0, 3, vocab_size=V,
                                prompt_len=(1, 4), new_tokens=(2, 5))
    for a, b in zip(reqs, reqs2):  # same seed → identical stream
        assert a.arrival_time == b.arrival_time
        assert np.array_equal(a.prompt, b.prompt)
        assert a.max_new_tokens == b.max_new_tokens
    for r in reqs:
        assert 1 <= len(r.prompt) <= 4
        assert 2 <= r.max_new_tokens <= 5
        assert ledger[r.rid]["prompt_len"] == len(r.prompt)


# ------------------------------------------------------- overload guard


def _req(rid, plen, owed, t=0.0):
    return Request(rid=rid, prompt=(np.arange(plen, dtype=np.int32) % V),
                   max_new_tokens=owed, arrival_time=t)


def _terminal_states(rep):
    """Every request must end in EXACTLY one terminal state."""
    out = {}
    for rid, s in rep.requests.items():
        states = [name for name, v in
                  (("finished", s.finished), ("rejected", s.rejected),
                   ("expired", s.expired)) if v is not None]
        assert len(states) == 1, (rid, states)
        out[rid] = states[0]
    return out


def test_overload_bounded_queue_no_starvation(setup):
    """2x-overload soak: the waiting line never exceeds max_queue (the
    excess is rejected at admission control, not silently buffered), no
    admitted request starves (FIFO order preserved), and every rid lands
    in exactly one terminal state with its full owed tokens if it
    finished."""
    model, params = setup
    cfg = ServeConfig(slots=1, max_len=64, prefill_chunk=8,
                      max_queue=3, step_time_s=0.01)
    eng = ServingEngine(model, params, cfg)
    reqs, ledger = poisson_workload(
        12, 40.0, seed=5, vocab_size=V, prompt_len=(2, 6),
        new_tokens=(8, 8))  # service ~12.5 req/s vs 40 qps offered
    rep = eng.run(reqs)

    states = _terminal_states(rep)
    assert rep.rejected > 0  # the guard actually engaged
    assert rep.peak_queue_depth <= cfg.max_queue
    admitted = [e[1] for e in rep.events if e[0] == "admit"]
    assert admitted == sorted(admitted)  # FIFO: arrival order == admit order
    for rid, state in states.items():
        if state == "finished":
            assert len(rep.requests[rid].tokens) == ledger[rid]["max_new_tokens"]
        else:
            assert state == "rejected"  # no deadline configured
    # Reject events carry slot -1 (never admitted).
    assert all(e[2] == -1 for e in rep.events if e[0] == "reject")


def test_deadline_expires_queued_and_midflight(setup):
    """One TTL, both expiry paths: the queued request dies waiting for
    the only slot (slot -1 in the event), the admitted one dies at a
    step boundary mid-generation (its slot id in the event) keeping its
    partial tokens in the ledger."""
    model, params = setup
    cfg = ServeConfig(slots=1, max_len=64, prefill_chunk=8,
                      deadline_s=0.2, step_time_s=0.01)
    eng = ServingEngine(model, params, cfg)
    rep = eng.run([_req(0, 4, 50), _req(1, 4, 4)])

    states = _terminal_states(rep)
    assert states == {0: "expired", 1: "expired"}
    r0, r1 = rep.requests[0], rep.requests[1]
    assert 0 < len(r0.tokens) < 50  # mid-flight: partial generation kept
    assert r0.finished is None
    assert len(r1.tokens) == 0  # starved in the queue, never admitted
    kinds = {e[1]: e for e in rep.events if e[0] == "expire"}
    assert kinds[0][2] == 0  # r0 expired IN its slot
    assert kinds[1][2] == -1  # r1 expired in the queue


def test_overload_run_is_deterministic(setup):
    """Same seed, same config -> byte-identical event log and ledger
    (the virtual step clock removes wall time from scheduling)."""
    model, params = setup
    cfg = ServeConfig(slots=2, max_len=64, prefill_chunk=8, max_queue=2,
                      deadline_s=0.5, step_time_s=0.01)

    def once():
        reqs, _ = poisson_workload(10, 30.0, seed=7, vocab_size=V,
                                   prompt_len=(2, 8), new_tokens=(4, 9))
        return ServingEngine(model, params, cfg).run(reqs)

    a, b = once(), once()
    assert a.events == b.events
    assert a.peak_queue_depth == b.peak_queue_depth
    assert _terminal_states(a) == _terminal_states(b)
    for rid in a.requests:
        assert a.requests[rid].tokens == b.requests[rid].tokens


def test_overload_config_validation():
    with pytest.raises(ValueError, match="max_queue"):
        ServeConfig(max_queue=0)
    with pytest.raises(ValueError, match="deadline_s"):
        ServeConfig(deadline_s=0.0)
    with pytest.raises(ValueError, match="step_time_s"):
        ServeConfig(step_time_s=-1.0)


# ------------------------------------------- one decode step kept in flight


def _fetch_then_dispatch(eng, reqs):
    """The order the loop had before it kept a step in flight, as the
    reference: admit into free slots, run one step, fetch it, commit it,
    and only then go round. Answers end by count; everybody is there at
    the start. -> ({rid: tokens}, the admit / evict events)."""
    b = eng.cfg.slots
    queue = sorted(reqs, key=lambda r: (r.arrival_time, r.rid))
    last, pos = np.zeros(b, np.int32), np.zeros(b, np.int32)
    owed, rid = np.zeros(b, int), np.full(b, -1)
    tokens, events, step = {r.rid: [] for r in reqs}, [], 0
    while queue or (rid >= 0).any():
        for i in range(b):
            if rid[i] < 0 and queue:
                req = queue.pop(0)
                pos[i], last[i] = eng._admit(i, req)
                owed[i], rid[i] = req.max_new_tokens, req.rid
                events.append(("admit", req.rid, i, step))
        state = ((np.stack([last, pos, rid >= 0]).astype(np.int32),)
                 if eng._stateful else (last.copy(), pos.copy()))
        out, _, eng.caches = eng._decode(eng.params, eng.caches, *state)
        step += 1
        for i, tok in enumerate(np.asarray(out)[:b].tolist()):
            if rid[i] < 0:
                continue
            tokens[rid[i]].append(tok)
            pos[i], last[i], owed[i] = pos[i] + 1, tok, owed[i] - 1
            if not owed[i]:
                events.append(("evict", int(rid[i]), i, step))
                rid[i] = -1
    return tokens, events, step


@pytest.mark.parametrize("build", [_model, _pattern_128],
                         ids=["stateless", "stateful"])
def test_step_in_flight_serves_the_fetch_then_dispatch_schedule(build):
    """With step N + 1 dispatched before step N is fetched, answers that
    end by count get the tokens, the slots, the steps and the event order
    of the loop that fetched first: an ending by count is known when its
    last step is dispatched, so its slot is refilled for the very next."""
    model = build()
    params, _ = model.init(jax.random.key(0))
    cfg = ServeConfig(slots=3, max_len=64, prefill_chunk=8)
    reqs, _ = poisson_workload(10, math.inf, 11, vocab_size=V,
                               prompt_len=(1, 20), new_tokens=(1, 8))
    want, events, steps = _fetch_then_dispatch(
        ServingEngine(model, params, cfg), reqs)
    eng = ServingEngine(model, params, cfg)
    assert eng._lookahead == 1
    rep = eng.run(reqs)
    assert {rid: st.tokens for rid, st in rep.requests.items()} == want
    assert rep.events == events
    assert rep.decode_steps == steps
    assert rep.busy_slot_steps == rep.generated_tokens  # no step discarded
    assert set(_terminal_states(rep).values()) == {"finished"}


def test_eos_ending_costs_one_discarded_step(setup):
    """An ending only the token tells is seen with the next step already
    in flight: that step's token for the slot is dropped (never in the
    ledger), the slot's next tenant comes one step later, and each request
    still ends exactly once."""
    model, params = setup
    reqs, _ = poisson_workload(8, math.inf, 5, vocab_size=V,
                               prompt_len=(2, 12), new_tokens=(4, 8))
    cfg = dict(slots=2, max_len=64, prefill_chunk=8)
    plain = ServingEngine(model, params, ServeConfig(**cfg)).run(reqs)
    eos = plain.requests[0].tokens[1]
    rep = ServingEngine(model, params, ServeConfig(eos_token=eos, **cfg)).run(reqs)

    assert set(_terminal_states(rep).values()) == {"finished"}
    early = []
    for rid, st in rep.requests.items():
        full = plain.requests[rid].tokens
        cut = full.index(eos) + 1 if eos in full else len(full)
        assert st.tokens == full[:cut]
        if cut < len(full):
            early.append(rid)
    assert 0 in early
    # one slot-step each, and no more, went to a request that had ended
    assert rep.busy_slot_steps - rep.generated_tokens == len(early)
    evicted = {e[1]: e for e in rep.events if e[0] == "evict"}
    for kind, rid, slot, step in rep.events:
        if kind != "admit" or step == 0:
            continue
        before = [e for e in evicted.values() if e[2] == slot and e[3] <= step]
        _, gone, _, freed = max(before, key=lambda e: e[3])
        assert step >= freed + (gone in early)


def test_midflight_deadline_costs_one_discarded_step(setup):
    """So does an ending only the clock tells: the expiry is seen at the
    commit of step N with N + 1 in flight, and N + 1's token is dropped."""
    model, params = setup
    cfg = ServeConfig(slots=1, max_len=64, prefill_chunk=8,
                      deadline_s=0.2, step_time_s=0.01)
    rep = ServingEngine(model, params, cfg).run([_req(0, 4, 50), _req(1, 4, 4)])
    assert _terminal_states(rep) == {0: "expired", 1: "expired"}
    r0 = rep.requests[0]
    # step k commits at (k + 1) x 0.01: the 21st token is the first past 0.2
    assert len(r0.tokens) == 21 and r0.expired == pytest.approx(0.21)
    assert rep.busy_slot_steps - rep.generated_tokens == 1
    assert rep.decode_steps == 22
    assert [e for e in rep.events if e[0] == "expire" and e[1] == 0] == [
        ("expire", 0, 0, 21)]


@pytest.mark.parametrize("kw,ahead", [({}, 1), (dict(spec_k=2), 0),
                                      (dict(cache_layout="paged", page_size=8), 0)],
                         ids=["dense", "spec", "paged"])
def test_dispatch_opens_before_the_fetch_of_the_step_before(setup, kw, ahead):
    """``serve/dispatch`` of step N + 1 opens before ``serve/fetch`` of step
    N and says ``ahead`` 1; the first step after an idle engine has nothing
    in flight before it and says 0, as does every step of an engine whose
    next inputs are data (speculative) or the commit's bookkeeping (paged)."""
    from tpudml.obs import Tracer, use_tracer

    model, params = setup
    cfg = ServeConfig(slots=2, max_len=64, prefill_chunk=8, step_time_s=0.01, **kw)
    tracer = Tracer()
    with use_tracer(tracer):  # the engine drains between the two arrivals
        rep = ServingEngine(model, params, cfg).run(
            [_req(0, 6, 5), _req(1, 9, 5, t=1.0)])
    assert set(_terminal_states(rep).values()) == {"finished"}
    spans = {name: {s.args["step"]: s for s in tracer.events
                    if (s.cat, s.name) == ("serve", name)}
             for name in ("dispatch", "fetch", "commit")}
    steps = sorted(spans["dispatch"])
    assert steps == sorted(spans["fetch"]) == sorted(spans["commit"])
    assert steps == list(range(rep.decode_steps))
    idle_before = {0, min(e[3] for e in rep.events if e[:2] == ("admit", 1))}
    for n in steps:
        d = spans["dispatch"][n]
        assert d.args["ahead"] == (ahead and n not in idle_before)
        assert spans["fetch"][n].ts_us <= spans["commit"][n].ts_us
        if n + 1 in spans["dispatch"]:
            nxt = spans["dispatch"][n + 1]
            assert nxt.args["ahead"] == (nxt.ts_us < spans["fetch"][n].ts_us)
    if ahead:
        assert sum(s.args["ahead"] for s in spans["dispatch"].values()) == len(steps) - 2


@pytest.mark.parametrize("build", [_model, _pattern_128],
                         ids=["stateless", "stateful"])
def test_a_stream_is_what_its_request_gets_alone(build):
    """Arrivals spread over the wall clock, so that passes find the engine
    idle, drained, mid-answer and refilling in turn: whatever the step in
    flight overlaps, every request is served the tokens it gets alone."""
    model = build()
    params, _ = model.init(jax.random.key(0))
    eng = ServingEngine(model, params,
                        ServeConfig(slots=3, max_len=64, prefill_chunk=8))
    reqs, _ = poisson_workload(24, 150.0, 5, vocab_size=V,
                               prompt_len=(1, 20), new_tokens=(1, 12))
    eng.run(reqs)  # compiles, so that the second run's passes are short
    rep = eng.run(reqs)
    assert rep.busy_slot_steps == rep.generated_tokens
    for r in reqs:
        alone = eng.run([Request(rid=r.rid, prompt=r.prompt,
                                 max_new_tokens=r.max_new_tokens)])
        assert rep.requests[r.rid].tokens == alone.requests[r.rid].tokens
