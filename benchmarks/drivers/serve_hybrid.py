"""Serving driver for a pattern model (`tpudml.models.HybridLM`, the
`nemotron_h` reference): `drivers/serve.py`'s run with this model's adapter,
reference and counts. What names neither is imported from there: the trace
thread, the warm-up, the traffic, the sample and the judgement.

`correct` follows the program's routing: the engine keeps every position's
expert choices (`RequestStats.routes`), the reference's expert layers take
those instead of choosing for themselves (`reference/nemotron_h.py` says why),
and the served tokens are judged in the logits it then gives. What that
leaves out, whether the choices themselves are right, `route_regret_mean`
reads: how far the program's choices lie from the reference's own, in the
score that chooses, over every position and expert layer of the sample.

The measured window lies in the steady state: the run serves `ramp_s` (the
cell file's) seconds of the same traffic first, which count as set-up, and
the `--seconds` after them are the window; the requests due inside it are the
window's. From an empty engine the tokens still owed when the window closes
(15 % of a run's: answers last up to 24 s) are all lost to it, and they grow
with every millisecond a pass takes, so `serve.tokens_per_s` read a tenth of
a slower host as 1.4 %; with as many owed when the window opens, it reads the
offered load (PERF.md, Findings, PR 30).

A `Tracer` records the engine's spans in every run. `decode_step_bytes`
follows the step's own counters (`counts_hybrid.py`): in a traced run the bytes
are the mean over the decode steps inside the traced window of what each step
had to move (its `active` slots from `serve/dispatch`, its `experts_touched`
from `serve/commit`). The info line names the run's longest pass."""

from __future__ import annotations

import gc
import statistics
import time

import numpy as np

from benchmarks import compare, counts_hybrid, tracing
from benchmarks import device as dev
from benchmarks.drivers import hybrid_adapter
from benchmarks.drivers.serve import (_trace_thread, judge_served, make_requests,
                                      pick_sample, warm_up)
from benchmarks.reference import nemotron_h as ref
from benchmarks.stats import percentile

PAD_TO = (1024, 3072)  # reference sequences are padded at the end to the first of these that
# holds them: two lengths, so two compiled programs a layer kind (they are most of a cold run's
# reference: ~35 s a length on the v5e)


def served_gaps(cfg: dict, weights: dict, sample: list, n_rows: int) -> list[dict]:
    """For each sampled request (prompt, served tokens, routes): run the
    reference once over the prompt with its served tokens along the
    program's routes, and at every served position read how far the served
    token's logit lies below the reference's best; at every position and
    expert layer, how far the program's choices lie from the reference's."""
    import jax.numpy as jnp

    out = []
    for prompt, served, routes in sample:
        seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        if routes.shape[0] != len(seq):
            raise ValueError(f"{routes.shape[0]} rows of routes for {len(seq)} positions")
        first_row = len(prompt) - 1
        length = next(n for n in PAD_TO if n >= max(len(seq), n_rows))
        padded = np.zeros(length, np.int32)
        padded[:len(seq)] = seq
        followed = np.zeros((length, routes.shape[1]), np.int32)  # padding: any expert
        followed[:len(seq)] = routes
        start = min(first_row, len(padded) - n_rows)  # keep the slice inside
        rows = slice(first_row - start, first_row - start + len(served))
        logits, regret = ref.served_rows_logits(cfg, weights, jnp.asarray(padded), start,
                                                n_rows, jnp.asarray(followed))
        logits = np.asarray(logits, np.float32)[rows]
        regret = np.asarray(regret, np.float32)[:, :len(seq)]
        gaps = logits.max(axis=-1) - logits[np.arange(len(served)), np.asarray(served)]
        out.append({"prompt_len": len(prompt), "tokens": len(served),
                    "max_gap": float(gaps.max()), "sum_gap": float(gaps.sum()),
                    "agree": int(np.sum(gaps == 0.0)),
                    "routings": int(regret.size), "sum_regret": float(regret.sum()),
                    "max_regret": float(regret.max()), "flips": int(np.sum(regret > 0.0))})
    return out


def finished_requests(reqs: list, report) -> list:
    """(rid, prompt, served tokens, routes [positions, n_E * k]) of every
    request that finished."""
    return [(r.rid, r.prompt, list(s.tokens), np.concatenate(s.routes))
            for r in reqs for s in [report.requests[r.rid]] if s.finished is not None]


def sample_of(finished: list, seed: int, n: int) -> list:
    """`pick_sample`'s requests, each with its routes."""
    routes = {id(tokens): r for _, _, tokens, r in finished}
    return [(prompt, tokens, routes[id(tokens)])
            for prompt, tokens in pick_sample([f[:3] for f in finished], seed, n)]


def judge(verdict: compare.Verdict, rows: list[dict], limits: dict) -> dict:
    """`judge_served`'s two numbers, and the mean regret of the program's
    routing over every position and expert layer of the sample (a router
    that rounds more flips more near-ties, by more: the mean grows with the
    square of its error)."""
    judged = judge_served(verdict, rows, limits)
    routings = sum(r["routings"] for r in rows)
    mean = sum(r["sum_regret"] for r in rows) / routings if routings else float("inf")
    flips = sum(r["flips"] for r in rows)
    verdict.add("route_regret_mean", mean, limits["route_regret_mean"],
                f"over {routings} routings, {flips} of them not the reference's own, widest "
                f"{max((r['max_regret'] for r in rows), default=0.0):.3g}")
    return {**judged, "routings_checked": routings,
            "routings_flipped": flips / routings if routings else None}


def make_weights(cell, seed: int):
    """The reference's flat weights from the seed, in the cell's stored type."""
    import jax

    dtype = hybrid_adapter.param_dtype(cell.spec["model"])
    return jax.jit(lambda key: ref.init_weights(cell.config, key, dtype))(ref.seed_key(seed))


def make_params(cell, seed: int):
    """The same weights as the program's parameter tree, in one program."""
    import jax

    cfg = cell.config
    dtype = hybrid_adapter.param_dtype(cell.spec["model"])
    return jax.jit(lambda key: hybrid_adapter.to_program(
        ref.init_weights(cfg, key, dtype), cfg))(ref.seed_key(seed))


def build_engine(cell, seed: int, **model_options):
    """The program's engine on weights from the seed. ``model_options``
    override the cell's (tools/control_hybrid.py: the `model` options of a
    `check.controls` entry, the model one precision down)."""
    from tpudml.serve.engine import ServeConfig, ServingEngine

    model = hybrid_adapter.build_model(cell.config, {**cell.spec["model"], **model_options})
    return ServingEngine(model, make_params(cell, seed),
                         ServeConfig(**cell.spec["engine"]["serve_config"]))


def step_bytes_from_spans(cfg: dict, spec: dict, events: list, lo_us: float,
                          hi_us: float) -> float | None:
    """Mean `counts_hybrid.decode_step_bytes` over the decode steps whose
    `serve/commit` lies in [lo_us, hi_us) of the tracer's clock; None when the
    program's spans carry no such counters."""
    import jax.numpy as jnp

    active = {e.args["step"]: e.args["active"] for e in events
              if e.cat == "serve" and e.name == "dispatch"}
    serve_cfg, model = spec["engine"]["serve_config"], spec["model"]
    sizes = dict(
        weight_bytes=jnp.dtype(hybrid_adapter.param_dtype(model)).itemsize,
        cache_bytes={"f32": 4, "bf16": 2, "int8": 1}[serve_cfg["cache_kind"]],
        state_bytes=jnp.dtype(model.get("state_dtype", "float32")).itemsize)
    per_step = [
        counts_hybrid.decode_step_bytes(cfg, active[e.args["step"]],
                                        e.args["experts_touched"], **sizes)
        for e in events
        if e.cat == "serve" and e.name == "commit" and lo_us <= e.ts_us < hi_us
        and "experts_touched" in (e.args or {}) and e.args["step"] in active]
    return statistics.mean(per_step) if per_step else None


def longest_pass(events: list) -> dict | None:
    """The longest `serve/iter` of the run and the spans inside it, so that a
    stall in an untraced run (seconds lost in one pass: PERF.md §7) says
    where it was: in admission's prefill, the dispatch, or the wait for the
    device."""
    passes = [e for e in events if e.cat == "serve" and e.name == "iter"]
    if not passes:
        return None
    worst = max(passes, key=lambda e: e.dur_us)
    inside: dict = {}
    for e in events:
        if (e.cat == "serve" and e is not worst and e.ts_us >= worst.ts_us
                and e.ts_us + e.dur_us <= worst.ts_us + worst.dur_us):
            inside[e.name] = round(inside.get(e.name, 0.0) + e.dur_us / 1e3, 3)
    return {"ms": worst.dur_us / 1e3, "at_s": worst.ts_us / 1e6, "step": worst.args["step"],
            "median_ms": statistics.median(e.dur_us for e in passes) / 1e3, "inside_ms": inside}


def run(cell, seed: int, seconds: float, trace: bool, devices, started: float,
        trace_dir: str) -> dict:
    from tpudml.obs.tracer import Tracer, use_tracer

    cfg, spec, traffic = cell.config, cell.spec, cell.traffic
    check = spec["check"]
    ramp = float(spec["ramp_s"])  # served before the window opens: set-up
    engine = build_engine(cell, seed)
    warm_up(engine, cell, seed)
    offered = make_requests(traffic, cfg, seed, ramp + seconds)
    reqs = [r for r in offered if r.arrival_time >= ramp]  # the window's
    gc.collect()

    window = tracing.TraceWindow(trace_dir) if trace else None
    tracer, start, length = None, 0.0, 0.0
    if window is not None:
        at = spec["trace"]
        start = ramp + min(at["start_s"], max(0.0, seconds - at["seconds"]) / 2)
        length = min(at["seconds"], seconds)
        tracer = _trace_thread(window, start, length)
    recorder = Tracer()  # every run: a few microseconds a span, ten spans a 50 ms pass
    t_run = time.perf_counter()
    with use_tracer(recorder):
        report = engine.run(offered)
    elapsed = time.perf_counter() - t_run
    if tracer is not None:
        tracer.join()
    setup_s = t_run + ramp - started
    memory_peak = dev.memory_peak_bytes(devices)
    step_bytes = None
    if window is not None:
        lo = (t_run - recorder._t0 + start) * 1e6  # the traced span on the recorder's clock
        step_bytes = step_bytes_from_spans(cfg, spec, recorder.events, lo, lo + length * 1e6)

    stats = [report.requests[r.rid] for r in reqs]
    bad = [s for s in report.requests.values() if s.finished is None]
    ttft = [(s.first_token if s.first_token is not None else report.wall_time)
            - s.arrival for s in stats]
    tpot = [s.tpot_s for s in stats if s.tpot_s is not None]
    waits = [s.admit_start - s.arrival for s in stats if s.admit_start is not None]
    generated = sum(len(s.tokens) for s in report.requests.values())
    # Completed inside the window, on the engine's clock (it starts with the
    # run), whoever asked: the ramp's answers that end in the window count, the
    # drain after the last arrival belongs to the tails, not here.
    in_window = sum(1 for s in report.requests.values() for t in s.token_times
                    if ramp < t <= ramp + seconds)

    # ---- the reference, after the engine's weights and cache are freed
    finished = finished_requests(reqs, report)
    owed = sum(1 for r in offered if report.requests[r.rid].finished is not None
               and len(report.requests[r.rid].tokens) != r.max_new_tokens)
    del engine
    gc.collect()
    t_ref = time.perf_counter()
    verdict = compare.Verdict()
    verdict.add("requests_not_finished", float(len(bad)), 0.0,
                f"of {len(offered)} offered, {len(reqs)} of them in the window")
    verdict.add("token_count_mismatch", float(owed), 0.0,
                "finished requests whose token count differs from what was asked")
    rows = []
    if finished:
        weights = make_weights(cell, seed)
        rows = served_gaps(cfg, weights, sample_of(finished, seed, check["sample"]),
                           traffic["output_len"]["max"])
        del weights
    judged = judge(verdict, rows, check["limits"])
    reference_s = time.perf_counter() - t_ref

    return {
        "verdict": verdict, "attempted": len(offered), "failed": len(bad),
        "end_to_end": {
            "serve.tpot_p95_ms": 1e3 * percentile(tpot, 95) if tpot else float("nan"),
            "serve.tokens_per_s": in_window / seconds,
            "setup_s": setup_s,
        },
        "memory_peak_bytes": memory_peak,
        "host": {"queue_waits_s": waits, "ttft_s": ttft, "tpot_s": tpot,
                 "decode_step_bytes": step_bytes},
        "info": {
            "requests": len(reqs), "generated_tokens": generated,
            "tokens_in_window": in_window,
            "prompt_tokens": int(sum(len(r.prompt) for r in reqs)),
            "ramp_s": ramp, "requests_offered": len(offered),
            # what a window opened on the empty engine would have counted (the docstring)
            "tokens_in_first_seconds": sum(1 for s in report.requests.values()
                                           for t in s.token_times if t <= seconds),
            "wall_s": report.wall_time, "drain_s": report.wall_time - ramp - seconds,
            "decode_steps": report.decode_steps, "occupancy": report.occupancy,
            "peak_queue_depth": report.peak_queue_depth,
            "ttft_p50_ms": 1e3 * percentile(ttft, 50),
            "ttft_p95_ms": 1e3 * percentile(ttft, 95),
            "tpot_p50_ms": 1e3 * percentile(tpot, 50) if tpot else None,
            "queue_wait_p50_ms": 1e3 * percentile(waits, 50) if waits else None,
            "decode_step_bytes": step_bytes, "longest_pass": longest_pass(recorder.events),
            **judged, "reference_s": reference_s, "elapsed_s": elapsed,
        },
    }
