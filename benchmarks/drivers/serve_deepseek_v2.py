"""Serving driver for the `deepseek_v2` reference's model (`tpudml.models.HybridLM`
with latent attention layers over a latent cache, softmax group-limited routing and
a shared expert): `drivers/serve_mimo.py`'s run with this model's adapter,
reference and counts. The drivers before it bind their models by import, so the
run is written out again here; what names no model is imported from
`drivers/serve.py` and `drivers/serve_hybrid.py`: the trace thread, the warm-up,
the traffic, the sample, the judgement, the finished requests and the longest
pass.

`correct` follows the program's routing, and the window lies in the steady
state behind the cell's `ramp_s`, both as `serve_hybrid.py` sets out. Reference
sequences are padded to the first of the cell's `check.pad_to` that holds them.

`decode_step_bytes` follows the step's own counters (`counts_deepseek_v2.py`):
the mean over the decode steps inside the traced window of what each step had to
move (its `rows_latent` and `active` from `serve/dispatch`, its
`experts_touched` from the same step's `serve/commit`)."""

from __future__ import annotations

import contextlib
import gc
import statistics
import time

import numpy as np

from benchmarks import compare, counts_deepseek_v2, tracing
from benchmarks import device as dev
from benchmarks.drivers import deepseek_v2_adapter as adapter
from benchmarks.drivers.serve import _trace_thread, make_requests, warm_up  # noqa: F401
from benchmarks.drivers.serve_hybrid import (finished_requests, judge, longest_pass,  # noqa: F401
                                             sample_of)
from benchmarks.reference import deepseek_v2 as ref
from benchmarks.stats import percentile

_CACHE_BYTES = {"f32": 4, "bf16": 2}

def served_gaps(cfg: dict, weights: dict, sample: list, n_rows: int, pad_to: list) -> list[dict]:
    """`serve_hybrid.served_gaps` against this reference: for each sampled
    request (prompt, served tokens, routes), the reference once over the prompt
    with its served tokens along the program's routes; at every served position
    how far the served token's logit lies below the reference's best, at every
    position and expert layer how far the program's choices lie from the
    reference's."""
    import jax.numpy as jnp

    out = []
    for prompt, served, routes in sample:
        seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        if routes.shape[0] != len(seq):
            raise ValueError(f"{routes.shape[0]} rows of routes for {len(seq)} positions")
        first_row = len(prompt) - 1
        length = next(n for n in pad_to if n >= max(len(seq), n_rows))
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


@contextlib.contextmanager
def outside_the_compile_cache():
    """What compiles inside is not written to the persistent cache (a lookup
    still hits what an earlier tree left there). For the reference's programs:
    `reference_s` lies behind the window and is no part of `setup_s`, and the
    chip's machine keeps 192 MiB for every cell's programs (`serve-code`'s
    alone are 150 MiB): the reference's 16 MiB there would buy `setup_s` nothing
    (PERF.md §6, PR 47)."""
    import jax

    name = "jax_persistent_cache_min_compile_time_secs"
    kept = getattr(jax.config, name)
    jax.config.update(name, 1e9)
    try:
        yield
    finally:
        jax.config.update(name, kept)


def make_weights(cell, seed: int):
    """The reference's flat weights from the seed, in the cell's stored type."""
    import jax

    dtype = adapter.param_dtype(cell.spec["model"])
    return jax.jit(lambda key: ref.init_weights(cell.config, key, dtype))(ref.seed_key(seed))


def make_params(cell, seed: int):
    """The same weights as the program's parameter tree, in one program."""
    import jax

    cfg = cell.config
    dtype = adapter.param_dtype(cell.spec["model"])
    return jax.jit(lambda key: adapter.to_program(
        ref.init_weights(cfg, key, dtype), cfg))(ref.seed_key(seed))


def build_engine(cell, seed: int, **model_options):
    """The program's engine on weights from the seed. ``model_options``
    override the cell's (tools/control_deepseek_v2.py: the `model` options of a
    `check.controls` entry)."""
    from tpudml.serve.engine import ServeConfig, ServingEngine

    model = adapter.build_model(cell.config, {**cell.spec["model"], **model_options})
    return ServingEngine(model, make_params(cell, seed),
                         ServeConfig(**cell.spec["engine"]["serve_config"]))


def step_counters(events: list, lo_us: float, hi_us: float) -> list[dict]:
    """The counters of the decode steps that are dispatched in [lo_us, hi_us) of
    the tracer's clock: `serve/dispatch`'s (`active`, `rows_latent`) with the same
    step's `serve/commit`'s (`experts_touched`). A program without them gives
    none."""
    commits = {e.args["step"]: e.args for e in events
               if e.cat == "serve" and e.name == "commit" and "experts_touched" in (e.args or {})}
    return [{**e.args, **commits[e.args["step"]]} for e in events
            if e.cat == "serve" and e.name == "dispatch" and lo_us <= e.ts_us < hi_us
            and "rows_latent" in (e.args or {}) and e.args["step"] in commits]


def step_bytes_from_spans(cfg: dict, spec: dict, steps: list[dict]) -> float | None:
    """Mean `counts_deepseek_v2.decode_step_bytes` over ``steps``; None when the
    program's spans carry no such counters."""
    import jax.numpy as jnp

    sizes = dict(
        weight_bytes=jnp.dtype(adapter.param_dtype(spec["model"])).itemsize,
        cache_bytes=_CACHE_BYTES[spec["engine"]["serve_config"]["cache_kind"]])
    per_step = [counts_deepseek_v2.decode_step_bytes(
        cfg, s["rows_latent"], s["active"], s["experts_touched"], **sizes) for s in steps]
    return statistics.mean(per_step) if per_step else None


def latent_step_means(cell, steps: list[dict]) -> dict:
    """For the info line, what no accepted reader reads of the traced window's
    decode steps (a `benchmark` PR's to declare: PERF.md, Open questions): the mean
    `active` and `rows_latent` a step (what `counts_deepseek_v2.decode_attn_counts`
    takes, `rows_latent` over the `L` layers), the share of the allocated latent
    rows that hold a token, and the share of the active tokens whose kept groups
    include this chip's (`moe_group_hit` x experts a token over `moe_routed`:
    3 / 8 where routing is even), both in percent. Nothing without the counters."""
    if not steps:
        return {}
    cfg, serve = cell.config, cell.spec["engine"]["serve_config"]
    rows = statistics.mean(s["rows_latent"] for s in steps)
    allocated = serve["slots"] * serve["max_len"] * cfg["hybrid_override_pattern"].count("L")
    routed = sum(s.get("moe_routed", 0) for s in steps)
    hits = sum(s.get("moe_group_hit", 0) for s in steps)
    return {"decode_active": statistics.mean(s["active"] for s in steps),
            "decode_rows_latent": rows, "latent_rows_live_share": 100.0 * rows / allocated,
            "moe_group_hit_share": (100.0 * cfg["num_experts_per_tok"] * hits / routed
                                    if routed else None)}


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
    recorder = Tracer()  # every run: a few microseconds a span, ten spans a pass
    t_run = time.perf_counter()
    with use_tracer(recorder):
        report = engine.run(offered)
    elapsed = time.perf_counter() - t_run
    if tracer is not None:
        tracer.join()
    setup_s = t_run + ramp - started
    memory_peak = dev.memory_peak_bytes(devices)
    step_bytes, steps = None, []
    if window is not None:
        lo = (t_run - recorder._t0 + start) * 1e6  # the traced span on the recorder's clock
        steps = step_counters(recorder.events, lo, lo + length * 1e6)
        step_bytes = step_bytes_from_spans(cfg, spec, steps)

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
        with outside_the_compile_cache():
            weights = make_weights(cell, seed)
            rows = served_gaps(cfg, weights, sample_of(finished, seed, check["sample"]),
                               traffic["output_len"]["max"], check["pad_to"])
        del weights
    judged = judge(verdict, rows, check["limits"])
    reference_s = time.perf_counter() - t_ref

    return {
        "verdict": verdict, "attempted": len(offered), "failed": len(bad),
        "end_to_end": {
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
            "wall_s": report.wall_time, "drain_s": report.wall_time - ramp - seconds,
            "decode_steps": report.decode_steps, "occupancy": report.occupancy,
            "peak_queue_depth": report.peak_queue_depth,
            "ttft_p50_ms": 1e3 * percentile(ttft, 50),
            "ttft_p95_ms": 1e3 * percentile(ttft, 95),
            "tpot_p50_ms": 1e3 * percentile(tpot, 50) if tpot else None,
            "tpot_p95_ms": 1e3 * percentile(tpot, 95) if tpot else None,
            "queue_wait_p50_ms": 1e3 * percentile(waits, 50) if waits else None,
            "decode_step_bytes": step_bytes, **latent_step_means(cell, steps),
            "longest_pass": longest_pass(recorder.events),
            **judged, "reference_s": reference_s, "elapsed_s": elapsed,
        },
    }
